"""Flash-attention tile table: tuned (block_q, block_k) shipped as data.

Upstream Horovod ships autotune results as runtime state discovered per job
(``horovod/runner/autotune``); on TPU the analogous knob is the pallas
flash-attention tiling, whose best value depends on (head_dim, seq, dtype,
kind-of-attention) and on VMEM pressure from the backward kernels — a pure
compile-time property of the shape, so it belongs in a checked-in table, not
a per-job search. ``flash_attention`` / ``ring_flash_attention`` /
``ulysses_attention`` consult this table whenever the caller does not pass
explicit tiles; ``autotune_flash_blocks(record=True)`` and
``tools/tune_tiles.py`` regenerate it from on-device measurements.

Table file: ``flash_tiles.json`` next to this module (override with
``HOROVOD_FLASH_TILE_TABLE=/path.json``). Schema::

    {"version": 1,
     "device": "tpu v5e",
     "default": {"block_q": 256, "block_k": 512},
     "entries": [{"head_dim": 64, "seq": 2048, "dtype": "bfloat16",
                  "kind": "causal", "block_q": 256, "block_k": 512,
                  "us_per_call": 950.0, "source": "tuned-v5e"}, ...]}

An entry from the forward + backward sweep (``tools/tune_tiles.py
--fwdbwd``, source ``tuned-*-fwdbwd``) may also carry ``block_q_bwd`` /
``block_k_bwd`` and, for a causal, window or block-diffusion shape,
``chunk`` / ``chunk_bwd``: the keys
of the resident K tile that one pass of the kernels' inner loop takes
(``lookup_full``).

``kind`` is one of "causal" | "full" | "ring" | "block_diffusion" | "window"
(the ring kernel's VMEM profile differs: its per-hop seq is the local shard
and the backward is an explicit second ring; a block-diffusion row is
``[noisy ; clean]``, ``seq`` counts both halves, and its tiles or chunks
are skipped along three diagonals; a window is a causal band, whose tiles
are skipped above the diagonal and under the band: an entry may say the
``window`` it was measured at, which no lookup reads). Lookup is
nearest-match: exact kind and dtype preferred, then closest head_dim and
seq in log space — so one measured point generalises to neighbouring shapes
until the tuner fills them in.
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
from pathlib import Path
from typing import Dict, List, Optional, Tuple

log = logging.getLogger("horovod_tpu")

__all__ = ["lookup", "lookup_full", "record", "load_table", "save_table",
           "table_path", "DEFAULT_TILES", "KINDS"]

DEFAULT_TILES = (256, 512)   # measured fastest on v5e for fwd+bwd (round 1)
KINDS = ("causal", "full", "ring", "block_diffusion", "window")

_lock = threading.Lock()
# path -> (mtime_ns, parsed table); one live version per path, so tuner
# writes to --out don't evict the shipped table between trace-time lookups.
_cache: Dict[str, Tuple[int, dict]] = {}


def table_path() -> Path:
    env = os.environ.get("HOROVOD_FLASH_TILE_TABLE")
    if env:
        return Path(env)
    return Path(__file__).with_name("flash_tiles.json")


def _empty_table() -> dict:
    return {"version": 1, "device": "unknown",
            "default": {"block_q": DEFAULT_TILES[0],
                        "block_k": DEFAULT_TILES[1]},
            "entries": []}


def load_table(path: Optional[os.PathLike] = None) -> dict:
    """Parse the tile table (cached on (path, mtime))."""
    p = Path(path) if path is not None else table_path()
    try:
        mtime = p.stat().st_mtime_ns
    except OSError:
        return _empty_table()
    key = str(p)
    with _lock:
        hit = _cache.get(key)
        if hit is None or hit[0] != mtime:
            try:
                with open(p) as f:
                    _cache[key] = (mtime, json.load(f))
            except (OSError, ValueError) as e:
                # Truncated/corrupt table: serve defaults, don't take
                # training down over a tuning hint — but say so (once:
                # the defaults are cached against this file version).
                log.warning("flash tile table %s is unreadable (%s); every "
                            "shape falls back to the default tiles %s",
                            p, e, DEFAULT_TILES)
                _cache[key] = (mtime, _empty_table())
        return _cache[key][1]


def save_table(table: dict, path: Optional[os.PathLike] = None) -> Path:
    p = Path(path) if path is not None else table_path()
    # Tolerate the same malformed entries lookup() tolerates — record()
    # must not crash after an hour-long sweep because an old entry is
    # missing a key.
    table["entries"] = sorted(
        table["entries"],
        key=lambda e: (str(e.get("kind", "")), str(e.get("dtype", "")),
                       str(e.get("head_dim", "")), str(e.get("seq", ""))))
    tmp = p.with_suffix(".json.tmp")
    with open(tmp, "w") as f:
        json.dump(table, f, indent=1)
        f.write("\n")
    os.replace(tmp, p)
    with _lock:
        _cache.clear()
    return p


def _distance(e: dict, head_dim: int, seq: int, dtype: str,
              kind: str) -> float:
    """Mismatch score; lower is better. Kind dominates, then dtype, then
    geometry in log space (a 2x-off seq beats a wrong-kind exact hit)."""
    d = 0.0
    if e["kind"] != kind:
        d += 1000.0
    if e["dtype"] != dtype:
        d += 100.0
    d += 10.0 * abs(math.log2(max(e["head_dim"], 1) / max(head_dim, 1)))
    d += abs(math.log2(max(e["seq"], 1) / max(seq, 1)))
    return d


def _best_entry(head_dim: int, seq: int, dtype: str, kind: str,
                path: Optional[os.PathLike]) -> Optional[dict]:
    """Nearest valid entry (valid = parseable positive fwd tiles), or
    None when the table is missing/empty/malformed."""
    table = load_table(path)
    best, best_d = None, float("inf")
    for e in table.get("entries") or []:
        try:
            d = _distance(e, head_dim, seq, dtype, kind)
            bq, bk = int(e["block_q"]), int(e["block_k"])
        except (KeyError, TypeError, ValueError):
            continue
        if bq <= 0 or bk <= 0:
            continue
        if d < best_d:
            best, best_d = e, d
    return best


def lookup(head_dim: int, seq: int, dtype, kind: str,
           path: Optional[os.PathLike] = None) -> Tuple[int, int]:
    """Best-known (block_q, block_k) for this attention shape.

    Falls back to the table's default (then ``DEFAULT_TILES``) when the
    table is missing or empty. Never raises on a malformed entry — the
    kernel clamps tiles to the sequence length anyway, and a bad table
    must not take training down.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown tile kind {kind!r}; expected one of "
                         f"{KINDS}")
    e = _best_entry(head_dim, seq, str(dtype), kind, path)
    if e is not None:
        return int(e["block_q"]), int(e["block_k"])
    try:
        default = load_table(path).get("default") or {}
        return (int(default.get("block_q", DEFAULT_TILES[0])),
                int(default.get("block_k", DEFAULT_TILES[1])))
    except (TypeError, ValueError, AttributeError):
        return DEFAULT_TILES


def lookup_full(head_dim: int, seq: int, dtype, kind: str,
                path: Optional[os.PathLike] = None
                ) -> Tuple[int, int, int, int, int, int]:
    """``(block_q, block_k, block_q_bwd, block_k_bwd, chunk, chunk_bwd)``
    for this shape.

    Backward-specific tiles exist only in ``tuned-*-fwdbwd`` entries (the
    differentiated-kernel sweep); entries without them — or with
    malformed bwd fields — and the table default reuse the forward tiles
    for the backward kernels, which is the pre-r5 behavior. Entry
    selection is shared with ``lookup`` (``_best_entry``), so the two can
    never disagree about the forward tiles.

    ``chunk`` is the compute chunk of the causal kernels: how many keys of
    the resident K tile one pass of the loop inside a grid step takes
    (``ops/flash_attention._chunk_loop``). An entry without one — every
    entry nobody has swept for it — yields ``block_k``, which is no loop:
    the kernels as they were. ``chunk_bwd`` is the backward kernels'; without
    one it is the forward's where they share the K tile, else
    ``block_k_bwd``.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown tile kind {kind!r}; expected one of "
                         f"{KINDS}")
    e = _best_entry(head_dim, seq, str(dtype), kind, path)
    if e is None:
        bq, bk = lookup(head_dim, seq, dtype, kind, path)  # default path
        return bq, bk, bq, bk, bk, bk
    bq, bk = int(e["block_q"]), int(e["block_k"])
    try:
        bqb, bkb = int(e.get("block_q_bwd") or bq), \
            int(e.get("block_k_bwd") or bk)
        if bqb <= 0 or bkb <= 0:
            bqb, bkb = bq, bk
    except (TypeError, ValueError):
        bqb, bkb = bq, bk
    chunk = _chunk(e.get("chunk"), bk, bk)
    return (bq, bk, bqb, bkb, chunk,
            _chunk(e.get("chunk_bwd"), bkb, chunk if bkb == bk else bkb))


def _chunk(said, block_k: int, otherwise: int) -> int:
    """An entry's compute chunk if it cuts ``block_k`` into whole parts,
    else ``otherwise`` (a malformed field is no field)."""
    try:
        chunk = int(said or 0)
    except (TypeError, ValueError):
        return otherwise
    return chunk if 0 < chunk <= block_k and block_k % chunk == 0 \
        else otherwise


def record(head_dim: int, seq: int, dtype, kind: str, block_q: int,
           block_k: int, us_per_call: Optional[float] = None,
           source: str = "tuned", device: Optional[str] = None,
           path: Optional[os.PathLike] = None,
           block_q_bwd: Optional[int] = None,
           block_k_bwd: Optional[int] = None,
           chunk: Optional[int] = None,
           chunk_bwd: Optional[int] = None,
           window: Optional[int] = None) -> Path:
    """Insert-or-replace one measured entry and rewrite the table file."""
    if kind not in KINDS:
        raise ValueError(f"unknown tile kind {kind!r}; expected one of "
                         f"{KINDS}")
    p = Path(path) if path is not None else table_path()
    table = load_table(p) if p.exists() else _empty_table()
    table = json.loads(json.dumps(table))   # private copy (cache aliases)
    if device:
        table["device"] = device
    key = (int(head_dim), int(seq), str(dtype), kind)
    table["entries"] = [
        e for e in table.get("entries", [])
        if (e.get("head_dim"), e.get("seq"), e.get("dtype"),
            e.get("kind")) != key]
    entry = {
        "head_dim": int(head_dim), "seq": int(seq), "dtype": str(dtype),
        "kind": kind, "block_q": int(block_q), "block_k": int(block_k),
        "us_per_call": (None if us_per_call is None
                        else round(float(us_per_call), 2)),
        "source": source}
    if block_q_bwd is not None:
        entry["block_q_bwd"] = int(block_q_bwd)
    if block_k_bwd is not None:
        entry["block_k_bwd"] = int(block_k_bwd)
    if chunk is not None:
        entry["chunk"] = int(chunk)
    if chunk_bwd is not None:
        entry["chunk_bwd"] = int(chunk_bwd)
    if window is not None:
        entry["window"] = int(window)
    table["entries"].append(entry)
    return save_table(table, p)
