"""ctypes bindings to the native runtime core (``cpp/libhvdtpu.so``).

The C++ layer owns host-side runtime concerns (SURVEY §2 row 11/16): the
multi-process coordinator + response cache, the fusion planner, the stall
inspector, and a fast chrome-trace appender. Pure-Python fallbacks keep the
framework importable if the toolchain is missing; ``native_available()``
reports which path is active.

Builds on demand with ``make`` (g++) on first use.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional

log = logging.getLogger("horovod_tpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CPP_DIR = os.path.join(_REPO, "cpp")
_SO_PATH = os.path.join(_CPP_DIR, "libhvdtpu.so")

_LOCK = threading.Lock()
_LIB = None
_TRIED = False


def _build() -> bool:
    try:
        subprocess.run(["make", "-C", _CPP_DIR], capture_output=True,
                       check=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        log.warning("native core: `make -C %s` failed (%s); using the "
                    "pure-Python fallbacks. %s", _CPP_DIR, e,
                    detail.decode(errors="replace")[-400:])
        return False
    return os.path.exists(_SO_PATH)


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if not os.path.exists(_SO_PATH) and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError:
            return None
        if not hasattr(lib, "hvd_pack_ffd"):
            # Stale .so predating the packer: rebuild + reload BEFORE any
            # ctypes bindings are set (bindings applied to an old handle
            # would be lost by the reload — a truncated c_int pointer
            # return corrupts every coordinator call). If the rebuild
            # fails, keep the OLD lib: packing falls back to Python
            # (pack_rows checks hasattr) but every other consumer works.
            if _build():
                try:
                    lib = ctypes.CDLL(_SO_PATH)
                except OSError:
                    pass          # keep the old handle
        lib.hvd_coord_create.restype = ctypes.c_void_p
        lib.hvd_coord_create.argtypes = [ctypes.c_int]
        lib.hvd_coord_destroy.argtypes = [ctypes.c_void_p]
        lib.hvd_coord_submit.restype = ctypes.c_int
        lib.hvd_coord_submit.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                         ctypes.c_char_p]
        lib.hvd_coord_pop_ready.restype = ctypes.c_int
        lib.hvd_coord_pop_ready.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                            ctypes.c_int]
        lib.hvd_coord_pending.restype = ctypes.c_int
        lib.hvd_coord_pending.argtypes = [ctypes.c_void_p]
        lib.hvd_cache_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_char_p]
        lib.hvd_cache_get.restype = ctypes.c_int
        lib.hvd_cache_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_char_p, ctypes.c_int]
        lib.hvd_cache_size.restype = ctypes.c_int
        lib.hvd_cache_size.argtypes = [ctypes.c_void_p]
        lib.hvd_fusion_plan.restype = ctypes.c_int
        lib.hvd_fusion_plan.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int64,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int32)]
        if hasattr(lib, "hvd_pack_ffd"):
            lib.hvd_pack_ffd.restype = ctypes.c_int
            lib.hvd_pack_ffd.argtypes = [
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
                ctypes.c_int64, ctypes.POINTER(ctypes.c_int32)]
        lib.hvd_stall_check.restype = ctypes.c_int
        lib.hvd_stall_check.argtypes = [ctypes.c_void_p, ctypes.c_double,
                                        ctypes.c_char_p, ctypes.c_int]
        lib.hvd_timeline_open.restype = ctypes.c_void_p
        lib.hvd_timeline_open.argtypes = [ctypes.c_char_p]
        lib.hvd_timeline_event.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char, ctypes.c_double, ctypes.c_double, ctypes.c_int,
            ctypes.c_int, ctypes.c_char_p]
        lib.hvd_timeline_now_us.restype = ctypes.c_double
        lib.hvd_timeline_now_us.argtypes = [ctypes.c_void_p]
        lib.hvd_timeline_close.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return _LIB


def native_available() -> bool:
    return load() is not None


class Coordinator:
    """Deterministic cross-process op ordering + response cache + stall
    inspection (native-backed; see cpp/hvdtpu_core.cpp)."""

    def __init__(self, world_size: int):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable (g++/make missing?)")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.hvd_coord_create(world_size))
        self.world_size = world_size

    def submit(self, rank: int, name: str) -> bool:
        """True when the op became ready (all ranks submitted)."""
        r = self._lib.hvd_coord_submit(self._h, rank, name.encode())
        if r < 0:
            raise ValueError(f"bad submit: rank={rank} name={name!r}")
        return bool(r)

    def pop_ready(self) -> Optional[str]:
        size = 1024
        while True:
            buf = ctypes.create_string_buffer(size)
            n = self._lib.hvd_coord_pop_ready(self._h, buf, size)
            if n == 0:
                return None
            if n > 0:
                return buf.value.decode()
            size = -n  # buffer too small; op not popped — retry larger

    def pending(self) -> int:
        return self._lib.hvd_coord_pending(self._h)

    def cache_put(self, key: str, value: str) -> None:
        self._lib.hvd_cache_put(self._h, key.encode(), value.encode())

    def cache_get(self, key: str) -> Optional[str]:
        size = 4096
        while True:
            buf = ctypes.create_string_buffer(size)
            n = self._lib.hvd_cache_get(self._h, key.encode(), buf, size)
            if n <= 0:
                return None
            if n < size:  # full value fit
                return buf.value.decode()
            size = n + 1  # truncated; n is the full length — retry

    def cache_size(self) -> int:
        return self._lib.hvd_cache_size(self._h)

    def stall_check(self, timeout_s: float) -> List[tuple]:
        """[(op_name, missing_rank_count)] for ops stuck > timeout."""
        size = 8192
        while True:
            buf = ctypes.create_string_buffer(size)
            n = self._lib.hvd_stall_check(self._h, timeout_s * 1e6, buf, size)
            if n == 0:
                return []
            if n > 0:
                break
            if n == -1:
                raise RuntimeError("stall_check failed")
            size = -n  # report didn't fit; retry with the needed size
        out = []
        for item in buf.value.decode().split(";"):
            if item:
                name, missing = item.rsplit(":", 1)
                out.append((name, int(missing)))
        return out

    def __del__(self):
        try:
            self._lib.hvd_coord_destroy(self._h)
        except Exception:
            pass


def fusion_plan(sizes_bytes: List[int], threshold_bytes: int,
                align_bytes: int = 512) -> Optional[List[int]]:
    """Bucket index per tensor (native greedy planner); None if the native
    library is unavailable (caller falls back to the Python planner)."""
    lib = load()
    if lib is None:
        return None
    n = len(sizes_bytes)
    if n == 0:
        return []
    sizes = (ctypes.c_int64 * n)(*sizes_bytes)
    out = (ctypes.c_int32 * n)()
    r = lib.hvd_fusion_plan(sizes, n, threshold_bytes, align_bytes, out)
    if r < 0:
        return None
    return list(out)


class NativeTimeline:
    """Chrome-trace writer backed by the C appender."""

    def __init__(self, path: str):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.hvd_timeline_open(path.encode()))
        if not self._h:
            raise OSError(f"cannot open timeline at {path}")
        self.path = path

    def now_us(self) -> float:
        return self._lib.hvd_timeline_now_us(self._h)

    def event(self, name: str, cat: str, ts_us: float, dur_us: float,
              pid: int = 0, tid: int = 0, ph: str = "X",
              args_json: str = "") -> None:
        self._lib.hvd_timeline_event(self._h, name.encode(), cat.encode(),
                                     ph.encode()[:1], ts_us, dur_us, pid,
                                     tid, args_json.encode())

    def close(self) -> None:
        if self._h:
            self._lib.hvd_timeline_close(self._h)
            self._h = None
