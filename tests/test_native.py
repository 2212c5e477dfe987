"""Native runtime core tests (cpp/libhvdtpu.so via ctypes)."""

import json
import os
import shutil
import subprocess
import threading
import time

import numpy as np
import pytest

from horovod_tpu import native

pytestmark = pytest.mark.skipif(not native.native_available(),
                                reason="native lib not built")


class TestCoordinator:
    def test_negotiation_ordering(self):
        """Ops become ready only when all ranks submitted, and pop in rank-0
        submission order regardless of other ranks' order."""
        c = native.Coordinator(3)
        assert not c.submit(0, "grad_b")   # rank 0 order: b then a
        assert not c.submit(0, "grad_a")
        assert not c.submit(1, "grad_a")
        assert not c.submit(2, "grad_b")   # still missing rank 1
        assert c.pop_ready() is None
        assert c.submit(1, "grad_b")       # b now ready (all 3)
        assert c.pop_ready() == "grad_b"
        assert c.pop_ready() is None       # a still missing rank 2
        assert c.submit(2, "grad_a")
        assert c.pop_ready() == "grad_a"
        assert c.pending() == 0

    def test_duplicate_submit_idempotent(self):
        c = native.Coordinator(2)
        c.submit(0, "x")
        c.submit(0, "x")
        assert c.pending() == 1
        assert c.submit(1, "x")
        assert c.pop_ready() == "x"

    def test_bad_rank(self):
        c = native.Coordinator(2)
        with pytest.raises(ValueError):
            c.submit(5, "x")

    def test_response_cache(self):
        c = native.Coordinator(2)
        assert c.cache_get("k") is None
        c.cache_put("k", "fused:0:1024")
        assert c.cache_get("k") == "fused:0:1024"
        assert c.cache_size() == 1

    def test_stall_inspector(self):
        c = native.Coordinator(4)
        c.submit(0, "stuck_op")
        c.submit(1, "stuck_op")
        time.sleep(0.05)
        report = c.stall_check(timeout_s=0.01)
        assert report == [("stuck_op", 2)]  # ranks 2,3 missing
        assert c.stall_check(timeout_s=10.0) == []


class TestFusionPlan:
    def test_threshold_buckets(self):
        plan = native.fusion_plan([400, 400, 400, 400], 800, align_bytes=1)
        assert plan == [0, 0, 1, 1]

    def test_oversize_tensor_own_bucket(self):
        plan = native.fusion_plan([100, 5000, 100], 1000, align_bytes=1)
        assert plan == [0, 1, 2]

    def test_alignment_padding(self):
        # two 300B tensors with 512B alignment -> 1024 > 800 threshold
        plan = native.fusion_plan([300, 300], 800, align_bytes=512)
        assert plan == [0, 1]

    def test_matches_python_fallback(self):
        rng = np.random.default_rng(0)
        sizes = [int(s) for s in rng.integers(1, 10_000, 200)]
        nat = native.fusion_plan(sizes, 16384, align_bytes=1)
        out, used, bucket = [], 0, -1
        for sz in sizes:
            if bucket < 0 or used + sz > 16384:
                bucket, used = bucket + 1, 0
            out.append(bucket)
            used += sz
        assert nat == out


class TestNativeTimeline:
    def test_write_and_parse(self, tmp_path):
        p = str(tmp_path / "nt.json")
        t = native.NativeTimeline(p)
        t0 = t.now_us()
        t.event("allreduce", "collective", t0, 120.0, pid=1, tid=2)
        t.event("broadcast", "collective", t0 + 200, 30.0)
        t.close()
        data = json.load(open(p))
        assert [e["name"] for e in data["traceEvents"]] == [
            "allreduce", "broadcast"]
        assert data["traceEvents"][0]["dur"] == 120.0


def test_build_puts_a_whole_library_in_place_or_none(tmp_path):
    """Six xdist workers of a fresh checkout call ``native.load()`` at
    once, and each builds: a reader must find no file or all of it. The
    stand-in compiler writes its output in two halves, slowly."""
    cpp = tmp_path / "cpp"
    shutil.copytree(os.path.join(native._REPO, "cpp"), cpp,
                    ignore=shutil.ignore_patterns("*.so"))
    slow = tmp_path / "slow_cxx.sh"
    slow.write_text('#!/bin/sh\nwhile [ "$1" != "-o" ]; do shift; done\n'
                    'printf half > "$2"; sleep 0.4; printf whole > "$2"\n')
    slow.chmod(0o755)
    seen = set()
    done = threading.Event()

    def reader():
        while not done.is_set():
            try:
                seen.add((cpp / "libhvdtpu.so").read_text())
            except FileNotFoundError:
                pass

    t = threading.Thread(target=reader)
    t.start()
    try:
        subprocess.run(["make", "-C", str(cpp), f"CXX={slow}"], check=True,
                       capture_output=True, timeout=60)
    finally:
        done.set()
        t.join()
    assert (cpp / "libhvdtpu.so").read_text() == "whole"
    assert seen <= {"whole"}, seen
    assert not [n for n in os.listdir(cpp) if ".tmp." in n]
