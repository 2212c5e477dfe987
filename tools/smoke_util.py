"""Shared harness for the 2-process smoke tools (trace/overlap/serve/
doctor/quant): retry once on gloo TCP rendezvous flakes.

Under a loaded CI host the ``jax.distributed`` rendezvous occasionally
fails — the coordinator's listener loses the bind race on a just-freed
port, or a worker's first connect times out before the coordinator is up
(the tier-1 flake noted in PR 5's run). That is environmental, not a
code failure, so each smoke's ``main()`` runs through
:func:`main_with_retry`: a first attempt whose failure output matches the
rendezvous signatures is retried ONCE — on a fresh port, since every
``run_smoke`` binds a new free port per call — and any second failure
(or any non-rendezvous failure) is reported as-is.

The tools run this module as a sibling import (``sys.path[0]`` is
``tools/`` when executed as scripts); tests exercise the tools end to
end as subprocesses, so the retry rides along.
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from horovod_tpu.utils import compile_cache  # noqa: E402


def jit_cache_env(env=None):
    """Worker env with a persistent XLA compilation-cache dir defaulted.

    The smokes respawn workers that compile the SAME tiny programs —
    every crash-loop attempt, rolling restart, and golden-then-faulted
    rerun pays a multi-second jit compile for an executable an earlier
    worker already built. Pointing every subprocess at one shared cache
    (entries are keyed on HLO + jax version, so staleness is impossible)
    makes only the first compile pay. The place follows the one rule in
    ``horovod_tpu/utils/compile_cache.py``: an inherited
    ``JAX_COMPILATION_CACHE_DIR`` is kept, else ``<checkout>/.jax_cache``.
    """
    env = dict(os.environ if env is None else env)
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache.cache_dir()
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    return env

#: failure-output signatures of a rendezvous/TCP-layer flake, not a code
#: bug: gloo/coordination-service connect errors, the distributed-init
#: deadline, and the freshly-freed-port bind race.
RENDEZVOUS_PATTERNS = (
    r"DEADLINE_EXCEEDED",
    r"UNAVAILABLE",
    r"[Cc]onnection refused",
    r"[Cc]onnection reset",
    r"[Ff]ailed to connect",
    r"[Aa]ddress already in use",
    r"[Bb]ind .*failed",
    r"coordination service.*(error|unavailable|not.*reach)",
    r"[Bb]arrier timed out",
    r"[Tt]imed out waiting for coordination",
    r"distributed\.initialize",
)

_RENDEZVOUS_RE = re.compile("|".join(RENDEZVOUS_PATTERNS))


def is_rendezvous_flake(text: str) -> bool:
    """Does this failure output look like a rendezvous/TCP flake?"""
    return bool(text) and _RENDEZVOUS_RE.search(text) is not None


#: tail of a failed attempt's collected output kept as evidence.
FAILURE_TAIL_LINES = 200


def _artifact_root() -> str:
    return os.environ.get(
        "HOROVOD_SMOKE_ARTIFACTS",
        os.path.join(tempfile.gettempdir(), "hvd_smoke_artifacts"))


def harvest_evidence(name: str, attempt: int, workdir: str,
                     failure_text: str) -> str:
    """Preserve a failed attempt's evidence before its workdir is
    destroyed: the collected worker/driver output tail plus any
    flight-recorder ``postmortem-*`` bundles published under the
    workdir (``HOROVOD_BLACKBOX``). A gloo-flake retry then no longer
    erases what the first attempt left behind. Returns the artifact
    dir."""
    import glob
    import shutil
    dst = os.path.join(_artifact_root(), name, f"attempt{attempt}")
    shutil.rmtree(dst, ignore_errors=True)
    os.makedirs(dst, exist_ok=True)
    tail = "\n".join(failure_text.splitlines()[-FAILURE_TAIL_LINES:])
    with open(os.path.join(dst, "failure.txt"), "w") as f:
        f.write(tail + "\n")
    for b in sorted(glob.glob(os.path.join(workdir, "**", "postmortem-*"),
                              recursive=True)):
        if not os.path.isdir(b):
            continue
        try:
            shutil.copytree(b, os.path.join(dst, os.path.basename(b)),
                            dirs_exist_ok=True)
        except OSError:
            continue
    return dst


def run_smoke(attempt_fn, name: str = "smoke", attempts: int = 2) -> int:
    """Run ``attempt_fn(workdir) -> (rc, failure_text)`` with the same
    rendezvous-flake retry policy as :func:`main_with_retry`, owning a
    fresh temporary ``workdir`` per attempt — and, on ANY failure,
    harvesting the attempt's evidence (output tail + postmortem
    bundles) into the artifact dir before the workdir is torn down."""
    rc, text = 1, ""
    for attempt in range(max(1, attempts)):
        with tempfile.TemporaryDirectory() as workdir:
            rc, text = attempt_fn(workdir)
            if rc != 0:
                where = harvest_evidence(name, attempt, workdir, text)
                print(f"{name}: attempt {attempt} failed; evidence "
                      f"saved to {where}", file=sys.stderr)
        if rc == 0:
            if attempt:
                print(f"{name}: passed on retry after a rendezvous flake",
                      file=sys.stderr)
            return 0
        if attempt + 1 < attempts and is_rendezvous_flake(text):
            print(f"{name}: rendezvous flake detected "
                  "(gloo TCP rendezvous failed); retrying once on a "
                  "fresh port", file=sys.stderr)
            continue
        break
    return rc


def main_with_retry(run, name: str = "smoke", attempts: int = 2) -> int:
    """Run ``run() -> (rc, failure_text)`` with one rendezvous retry.

    ``run`` returns exit status plus the collected worker/driver output
    of a failed attempt (empty string on success). A failing attempt
    whose output matches :data:`RENDEZVOUS_PATTERNS` is retried (each
    ``run`` call binds a fresh port); anything else fails immediately.
    """
    rc, text = 1, ""
    for attempt in range(max(1, attempts)):
        rc, text = run()
        if rc == 0:
            if attempt:
                print(f"{name}: passed on retry after a rendezvous flake",
                      file=sys.stderr)
            return 0
        if attempt + 1 < attempts and is_rendezvous_flake(text):
            print(f"{name}: rendezvous flake detected "
                  "(gloo TCP rendezvous failed); retrying once on a "
                  "fresh port", file=sys.stderr)
            continue
        break
    return rc
