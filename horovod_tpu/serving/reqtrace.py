"""Request-scoped distributed tracing: follow ONE request from client
submit to the last pushed token.

The training-side observability layers key on collective op-ids
(``tracing.py`` mints one per eager collective; ``trace_merge.py``
correlates them across rank shards). Serving has no such spine: a
request's life crosses a dispatcher process, the wire, a replica's
queue, the paged cache, and the push pump — and when p99 TTFT degrades
the ``serve_*`` histograms say *that* it degraded, never *where*. This
module is the per-request correlation layer:

* A **trace context** (``trace_id`` + parent span id) is minted at
  ``Dispatcher``/``RemoteDispatcher`` submit and rides the submit RPC
  payload (both the legacy JSON wire and the v2 stream frames carry the
  params dict unchanged, so one ``"trace"`` key covers both protocols)
  and is stamped onto the engine-side
  :class:`~horovod_tpu.serving.scheduler.Request`.
* Every hop emits **spans** into a bounded in-process buffer —
  client-side ``SUBMIT``/``ATTEMPT``/``RETRY``/``HEDGE``/
  ``BREAKER_WAIT``/``CLIENT_FIRST_TOKEN``, server-side ``QUEUE``/
  ``ADMIT``/``PREFILL`` (one per chunk)/``DECODE`` (sampled every
  ``HOROVOD_REQUEST_TRACE_DECODE_EVERY`` steps)/``COW``/
  ``FIRST_TOKEN``/``PUSH_DELIVERY``. Disaggregated serving
  (serving/disagg.py) adds the migration legs: ``KV_EXPORT`` (prefill
  engine writes the request's KV onto the export hook) and
  ``KV_GRAFT`` (decode engine imports it) as server-side instants,
  plus the dispatcher-side ``MIGRATE`` span (fetch + graft, with
  ``src``/``dst``/``bytes``/``frames`` args) and the
  ``MIGRATE_FALLBACK`` instant when a lost leg downgrades the request
  to a monolithic re-prefill.
* :func:`flush` writes the buffer as a Chrome-trace shard
  (``reqtrace.<label>.<pid>.json`` under
  ``HOROVOD_REQUEST_TRACE_DIR``) whose ``shard_meta`` carries
  ``role: "request"`` and a wall-clock origin, so
  ``trace_merge.merge_timelines`` threads request tracks through the
  collective tracks on one timeline and
  ``trace_merge.request_report()`` computes per-request critical paths.

Everything here is host-side Python — no jit interaction, so the
engine's ``decode_compiles == 1`` contract survives tracing on. Off by
default; ``HOROVOD_REQUEST_TRACE=1`` enables it. Span emission never
raises into a serving hot path, and the buffer is a bounded deque
(oldest spans drop first on overflow).

Span event shape (Chrome trace, ``cat="request"``): ``ts`` is
microseconds since this process's trace origin (``wall0``, wall-clock
seconds, recorded in ``shard_meta``); ``args`` always carry
``trace_id``, ``span_id``, and ``parent_id`` so a request's spans chain
across processes.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

__all__ = ["TraceContext", "mint_context", "enabled", "span", "emit",
           "instant", "events", "reset", "flush", "SPAN_KINDS"]

#: the span kinds, for docs and tooling (client side, then server side)
SPAN_KINDS = (
    "SUBMIT", "ATTEMPT", "RETRY", "HEDGE", "HEDGE_WIN", "BREAKER_WAIT",
    "CLIENT_FIRST_TOKEN", "MIGRATE", "MIGRATE_FALLBACK",
    "QUEUE", "ADMIT", "PREFILL", "DECODE", "COW", "FIRST_TOKEN",
    "PUSH_DELIVERY", "KV_EXPORT", "KV_GRAFT",
)

#: bounded span buffer cap — ~16k spans is minutes of traced serving;
#: overflow drops the OLDEST spans (deque semantics), never blocks.
BUFFER_CAP = 16384

_LOCK = threading.Lock()
_SPAN_SEQ = itertools.count(1)
_BUF: deque = deque(maxlen=BUFFER_CAP)
_DROPPED = 0
_WALL0: Optional[float] = None
_ATEXIT_REGISTERED = False


def enabled() -> bool:
    """Is request tracing on (``HOROVOD_REQUEST_TRACE=1``)? Reads the
    resolved config; never raises (import failures read as off)."""
    try:
        from horovod_tpu.config import get_config
        return bool(get_config().request_trace)
    except Exception:
        return False


class TraceContext:
    """Identity one request's spans share: ``tid`` (the trace id, one
    per request) plus this hop's span id. Serialize with :meth:`wire`
    (a plain dict that rides the submit RPC params on both wire
    protocols); every span emitted against a context mints its own
    span id with the context's ``sid`` as parent."""

    __slots__ = ("tid", "sid")

    def __init__(self, tid: str, sid: Optional[int] = None):
        self.tid = str(tid)
        self.sid = int(sid) if sid is not None else next(_SPAN_SEQ)

    def wire(self) -> Dict[str, Any]:
        return {"tid": self.tid, "sid": self.sid}

    def __repr__(self) -> str:
        return f"TraceContext(tid={self.tid!r}, sid={self.sid})"


def mint_context() -> TraceContext:
    """Mint a fresh trace context at the submit boundary (dispatcher)."""
    return TraceContext(uuid.uuid4().hex[:16])


def _tr_fields(tr: Any) -> Optional[Dict[str, Any]]:
    """Normalize a context argument — a :class:`TraceContext`, a wire
    dict, or garbage from an untrusted payload — to (tid, parent sid).
    Returns ``None`` when there is nothing trace-shaped to attach to."""
    if isinstance(tr, TraceContext):
        return {"tid": tr.tid, "parent": tr.sid}
    if isinstance(tr, dict) and tr.get("tid"):
        try:
            return {"tid": str(tr["tid"]), "parent": int(tr.get("sid", 0))}
        except (TypeError, ValueError):
            return None
    return None


def _wall0() -> float:
    global _WALL0
    if _WALL0 is None:
        with _LOCK:
            if _WALL0 is None:
                _WALL0 = time.time()
    return _WALL0


def _record(name: str, ph: str, t0_wall: float, dur_s: float, tr: Any,
            args: Dict[str, Any]) -> None:
    global _DROPPED
    f = _tr_fields(tr)
    if f is None:
        return
    try:
        ev_args = {"trace_id": f["tid"], "span_id": next(_SPAN_SEQ),
                   "parent_id": f["parent"]}
        ev_args.update(args)
        ev: Dict[str, Any] = {
            "name": name, "cat": "request", "ph": ph,
            "ts": (t0_wall - _wall0()) * 1e6,
            "pid": os.getpid(), "tid": 0, "args": ev_args}
        if ph == "X":
            ev["dur"] = max(0.0, float(dur_s)) * 1e6
        if ph == "i":
            ev["s"] = "g"
        with _LOCK:
            if len(_BUF) == _BUF.maxlen:
                _DROPPED += 1
            _BUF.append(ev)
        _maybe_register_flush()
    except Exception:
        pass                       # never raise into a serving hot path


def emit(name: str, tr: Any, t0_wall: float, dur_s: float,
         **args: Any) -> None:
    """Record one complete span (``ph="X"``): it started at ``t0_wall``
    (wall-clock seconds, ``time.time()``) and lasted ``dur_s``."""
    _record(name, "X", t0_wall, dur_s, tr, args)


def instant(name: str, tr: Any, **args: Any) -> None:
    """Record one instant event (``ph="i"``) at now."""
    _record(name, "i", time.time(), 0.0, tr, args)


@contextmanager
def span(name: str, tr: Any, **args: Any):
    """Context manager measuring one wall-clock span around a block."""
    t0 = time.time()
    try:
        yield
    finally:
        emit(name, tr, t0, time.time() - t0, **args)


def events() -> List[Dict[str, Any]]:
    """Snapshot of the live span buffer (what ``/trace`` serves and what
    ``tools/serve_bench.py`` feeds into ``trace_merge.request_report``)."""
    with _LOCK:
        return list(_BUF)


def reset() -> None:
    """Drop the buffer and the trace origin (tests)."""
    global _WALL0, _DROPPED
    with _LOCK:
        _BUF.clear()
        _WALL0 = None
        _DROPPED = 0


def _proc_label() -> str:
    label = os.environ.get("HOROVOD_REQTRACE_LABEL")
    return label if label else f"pid{os.getpid()}"


def shard_basename() -> str:
    """This process's shard file name under the trace dir."""
    return f"reqtrace.{_proc_label()}.{os.getpid()}.json"


def flush(path: Optional[str] = None) -> Optional[str]:
    """Write the buffered spans as one Chrome-trace shard and return its
    path (``None`` when there is nowhere to write: no explicit ``path``
    and ``HOROVOD_REQUEST_TRACE_DIR`` unset, or an empty buffer).

    The shard leads with a ``process_name`` metadata row and a
    ``shard_meta`` marker carrying ``role: "request"`` plus ``wall0``
    (this process's trace origin, wall-clock seconds) — that is how
    ``trace_merge`` tells request shards apart from collective rank
    shards and aligns their clocks without a collective anchor."""
    if path is None:
        try:
            from horovod_tpu.config import get_config
            trace_dir = get_config().request_trace_dir
        except Exception:
            trace_dir = None
        if not trace_dir:
            return None
        path = os.path.join(trace_dir, shard_basename())
    with _LOCK:
        evs = list(_BUF)
        dropped = _DROPPED
    if not evs:
        return None
    pid = os.getpid()
    label = _proc_label()
    head: List[Dict[str, Any]] = [
        {"name": "process_name", "cat": "__metadata", "ph": "M",
         "ts": 0.0, "pid": pid, "tid": 0,
         "args": {"name": f"request {label}"}},
        {"name": "shard_meta", "cat": "trace", "ph": "i", "ts": 0.0,
         "pid": pid, "tid": 0, "s": "g",
         "args": {"role": "request", "proc": label, "pid": pid,
                  "wall0": _wall0(), "dropped": dropped}},
    ]
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{pid}"
    with open(tmp, "w") as f:
        json.dump({"traceEvents": head + evs, "displayTimeUnit": "ms"},
                  f, default=str)
    os.replace(tmp, path)
    return path


def _maybe_register_flush() -> None:
    """First span with a trace dir configured registers an atexit flush,
    so short-lived processes (replicas, bench runs) land their shard
    without an explicit flush call — mirrors the timeline's atexit."""
    global _ATEXIT_REGISTERED
    if _ATEXIT_REGISTERED:
        return
    try:
        from horovod_tpu.config import get_config
        if not get_config().request_trace_dir:
            return
    except Exception:
        return
    with _LOCK:
        if _ATEXIT_REGISTERED:
            return
        _ATEXIT_REGISTERED = True
    import atexit
    atexit.register(flush)
