"""conftest's guard on the session's ``hvd`` state: each kind of state a
test file can leave changed is named with the file, and put back."""

import jax
import pytest

import horovod_tpu as hvd
from conftest import hold_to_session_state, session_state_faults


def _shrink_world(tmp_path):
    hvd.init(devices=jax.devices()[:2])


def _shut_down(tmp_path):
    hvd.shutdown()


def _leave_timeline_started(tmp_path):
    hvd.start_timeline(str(tmp_path / "left.json"))


def _leave_process_set(tmp_path):
    hvd.add_process_set([0, 1])


@pytest.mark.parametrize("leak, named", [
    (_shrink_world, "world left at 2 devices"),
    (_shut_down, "hvd left shut down"),
    (_leave_timeline_started, "timeline left started"),
    (_leave_process_set, r"process sets \[1\] left registered"),
])
def test_guard_names_the_file_and_restores(leak, named, tmp_path):
    assert session_state_faults() == []
    leak(tmp_path)
    try:
        with pytest.raises(pytest.fail.Exception,
                           match=f"tests/test_culprit.py left .*{named}"):
            hold_to_session_state("tests/test_culprit.py")
    finally:
        if session_state_faults():      # the guard itself is at fault
            hvd.stop_timeline()
            hvd.init()
    assert session_state_faults() == []
    assert hvd.size() == 8 and hvd.is_initialized()
    hold_to_session_state("tests/test_culprit.py")      # silent when clean
