"""Test harness: virtual 8-device CPU mesh (SURVEY §4).

Must set platform flags before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

# Persistent XLA compilation cache, shared by this process AND every
# smoke-tool subprocess (workers inherit the env): the suite compiles
# the same tiny programs dozens of times — every fleet respawn, every
# golden-then-faulted rerun, every restarted elastic worker. Entries
# are keyed on the HLO + jax version, so staleness is impossible by
# construction; only compiles slower than the threshold are written.
from horovod_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _init_hvd():
    hvd.init()
    assert hvd.size() == 8, f"expected 8 virtual devices, got {hvd.size()}"
    yield


def session_state_faults():
    """What of the session's global state is not as ``_init_hvd`` left it:
    one line a kind, empty when a file put back all it changed."""
    from horovod_tpu import process_set, timeline
    if not hvd.is_initialized():
        return ["hvd left shut down"]
    faults = []
    if hvd.size() != 8:
        faults.append(f"world left at {hvd.size()} devices, not 8")
    if timeline.get_timeline() is not None:
        faults.append(f"timeline left started "
                      f"({timeline.get_timeline().path})")
    extra = sorted(set(process_set.get_process_set_ids_and_ranks()) - {0})
    if extra:
        faults.append(f"process sets {extra} left registered")
    return faults


def hold_to_session_state(file):
    """Fail, naming ``file``, if the session's state is not as it was, and
    put it back first so that no stranger fails for it: ``hvd.init``
    resets the process sets itself; a timeline is stopped before it, or
    init keeps it."""
    faults = session_state_faults()
    if faults:
        hvd.stop_timeline()
        hvd.init()
        pytest.fail(f"{file} left the session's hvd state changed: "
                    f"{'; '.join(faults)} (restored)", pytrace=False)


@pytest.fixture(scope="module", autouse=True)
def _session_state_guard(request):
    """Every file shares one process's ``hvd`` state with the files its
    xdist worker runs next. A file that changes it puts it back; one that
    does not is named here, on its own last test."""
    yield
    hold_to_session_state(request.module.__file__)


@pytest.fixture
def rng():
    import numpy as np
    return np.random.default_rng(42)


def stripe_seq(x, n):
    """Reorder axis 1 so shard_map's contiguous split hands device r the
    striped subset (positions r, r+n, r+2n, ...) — the striped ring layout
    convention shared by the attention/gpt2 tests."""
    import numpy as np
    x = np.asarray(x)
    return np.concatenate([x[:, r::n] for r in range(n)], axis=1)


def unstripe_seq(y, n):
    import numpy as np
    y = np.asarray(y)
    out = np.empty_like(y)
    t = y.shape[1] // n
    for r in range(n):
        out[:, r::n] = y[:, r * t:(r + 1) * t]
    return out
