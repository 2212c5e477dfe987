"""Overlapped gradient synchronization: algorithm selection, chunked
reduce-scatter + all-gather pipelines, and backward taps.

The reference Horovod's whole reason to exist is hiding communication
behind backward compute (``controller.cc`` cycle-time batching). This
module is that layer for the TPU rebuild, in the three places XLA gives
us leverage (the interleaving itself is the TPU compiler's scheduler,
which takes no flag from here):

* **Algorithm selection** (:func:`resolve_algorithm`): every allreduce
  bucket can lower to the single ``psum`` (XLA's own all-reduce) or to a
  reduce-scatter + all-gather decomposition (``lax.psum_scatter`` +
  ``lax.all_gather`` — the classic 2(n-1)/n-traffic ring split;
  PAPERS.md "Swing", and the RS+AG shape ``optimizer_sharded.py``
  already proves out for the weight update). ``auto`` resolves to
  ``psum`` on the exact wire at every size, because on the one fabric
  timed (a v5e 2x2) every decomposition lost to it; under a quantized
  wire, which needs the decomposition to quantize inside, it picks by
  size: RS+AG for large buckets, the **chunked** pipeline for the
  largest.
* **Chunked pipelining** (:func:`chunked_rs_ag_psum`): a big bucket is
  split into K chunks whose reduce-scatters are issue-ordered with
  ``lax.optimization_barrier`` so XLA can run chunk i's all-gather
  concurrently with chunk i+1's reduce-scatter (and with surrounding
  compute).
* **Backward taps** (:func:`make_grad_sync_tap` / :func:`tap_params`):
  ``custom_vjp`` identities on parameter groups whose backward rule
  allreduces the incoming cotangent — collectives are issued *inside*
  the backward in reverse-production order (last layer's grads first)
  instead of after one barrier at the end, which is exactly the overlap
  the reference's ready-ordering machinery bought on GPUs.

Everything here is trace-time: sizes are static python ints, so
selection/chunking never fragments the compile cache beyond the knobs
the user actually turned.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu import metrics as _metrics

__all__ = [
    "ALGORITHMS", "WIRES", "resolve_algorithm", "parse_algorithm",
    "compose_algorithm", "wire_bytes", "wire_bytes_by_phase",
    "rs_ag_psum", "chunked_rs_ag_psum",
    "rs_ag_2d_psum", "chunked_rs_ag_2d_psum", "swing_psum",
    "make_grad_sync_tap", "tap_params",
    "RS_AG_MIN_BYTES", "CHUNKED_MIN_BYTES",
]

log = logging.getLogger("horovod_tpu")

#: the ``algorithm=`` axis of ``hvd.allreduce``. The ``_int8``/``_fp8``
#: variants run the same RS+AG decomposition with an EQuARX-style 1-byte
#: wire: each chunk is block-quantized before its reduce-scatter leg,
#: reduced exactly in fp32 at the owning shard, re-quantized for the
#: all-gather leg, with per-block fp32 scales riding alongside — the wire
#: carries quantized bytes end to end (see ``ops/quantized.py``).
#: The ``_2d`` family lowers the same bucket as a multi-phase torus
#: reduction (PAPERS.md arxiv 2011.03605): reduce-scatter along each
#: detected torus dim in turn, all-gather back in reverse, every phase
#: riding a shorter sub-ring. ``swing`` is the distance-halving
#: logical-to-physical schedule (PAPERS.md arxiv 2401.09356) for
#: latency-bound buckets — log2(n) exchange steps instead of a ring's
#: 2(n-1), exact wire only, power-of-two worlds.
ALGORITHMS = ("auto", "psum", "rs_ag", "chunked_rs_ag",
              "rs_ag_int8", "chunked_rs_ag_int8",
              "rs_ag_fp8", "chunked_rs_ag_fp8",
              "rs_ag_2d", "chunked_rs_ag_2d",
              "rs_ag_2d_int8", "chunked_rs_ag_2d_int8",
              "rs_ag_2d_fp8", "chunked_rs_ag_2d_fp8",
              "swing")

#: the ``HOROVOD_ALLREDUCE_WIRE`` axis (config.py): the default payload
#: precision on the allreduce wire. ``fp32`` = whatever the bucket dtype
#: is (no recoding), ``bf16`` = cast for the collective and back, ``int8``
#: / ``fp8`` = block-scaled quantization inside the RS+AG decomposition
#: (``auto`` algorithm resolution upgrades rs_ag picks to the quantized
#: variant; explicit ``psum`` stays exact).
WIRES = ("fp32", "bf16", "int8", "fp8")

#: wire formats that restructure the reduction (quantized payloads)
QUANT_WIRES = ("int8", "fp8")


def parse_algorithm(algorithm: str):
    """Split an algorithm name into ``(base, wire)`` — e.g.
    ``"chunked_rs_ag_int8" -> ("chunked_rs_ag", "int8")``;
    unquantized names return ``(name, None)``."""
    for w in QUANT_WIRES:
        if algorithm.endswith("_" + w):
            return algorithm[: -len(w) - 1], w
    return algorithm, None


def compose_algorithm(base: str, wire) -> str:
    """Attach a wire format to a base algorithm name. ``fp32``/``bf16``/
    ``None`` leave the base unchanged (bf16 is a cast around the
    collective, not a restructured reduction); ``psum`` has no RS+AG
    shape to quantize inside and stays exact, and ``swing`` is exact by
    construction (its blocks change owner every step, so there is no
    single re-quantization point that keeps ranks bit-identical)."""
    if wire not in QUANT_WIRES or base in ("psum", "swing"):
        return base
    return f"{base}_{wire}"

# auto-selection size cutoffs, per fusion bucket, under a quantized wire
# (HOROVOD_ALLREDUCE_WIRE=int8|fp8), whose payload is quantized inside
# the decomposition. Below RS_AG_MIN the exact one-op psum stays; above
# it the bucket takes RS+AG; above CHUNKED_MIN it is split into
# pipelined chunks. On the exact wire `auto` is psum at every size and
# these do not apply. The sweep that set that rule (PERF.md section 6,
# PR 32: GPT-2 medium on a v5e 2x2, 1,419 MB of fp32 gradients a step in
# 28 buckets of up to 64 MB, ms a step / ms in collectives): psum 243.0 /
# 24.6, rs_ag 282.1 / 36.3, chunked_rs_ag 289.3 / 47.7, rs_ag_2d 322.2 /
# 73.7, chunked_rs_ag_2d (what auto resolved to until then) 341.4 / 81.5.
# The TPU compiler keeps no reduce-scatter: each psum_scatter becomes a
# whole all-reduce and a dynamic-slice, so a decomposition is psum plus
# its all-gathers and the padding, slicing and copying round them.
RS_AG_MIN_BYTES = 4 * 1024 * 1024
CHUNKED_MIN_BYTES = 32 * 1024 * 1024

#: default chunk count for ``chunked_rs_ag`` (HOROVOD_OVERLAP_CHUNKS)
DEFAULT_CHUNKS = 4


def _reject_algorithm(requested: str, knob: Optional[str] = None) -> None:
    """Raise the algorithm-rejection error, naming the composed form the
    caller actually received (base + wire suffix) and the knob that set
    it — a bare ``expected one of ALGORITHMS`` hides that e.g.
    ``"swing_int8"`` was built by composing a valid base with ``wire=``.
    """
    knobs = knob or ("algorithm= / HOROVOD_ALLREDUCE_ALGORITHM")
    base, qw = parse_algorithm(requested)
    if qw is not None and base in ALGORITHMS:
        raise ValueError(
            f"allreduce algorithm {requested!r} (base {base!r} composed "
            f"with wire={qw!r}) has no quantized lowering: {base!r} is "
            f"exact by construction. Drop the wire "
            f"(wire= / HOROVOD_ALLREDUCE_WIRE) or pick an rs_ag-family "
            f"base via {knobs}.")
    raise ValueError(
        f"unknown allreduce algorithm {requested!r} (set via {knobs}); "
        f"expected one of {ALGORITHMS} — quantized variants compose as "
        f"<base>_int8 / <base>_fp8.")


def _torus_ndims(topology) -> int:
    """Number of non-degenerate torus dims (``None``/1-D ring -> 1)."""
    if not topology:
        return 1
    return max(1, sum(1 for d in topology if int(d) > 1))


def resolve_algorithm(requested: str, nbytes: int, op: int, world: int,
                      reducible: bool, wire: Optional[str] = None,
                      topology: Optional[Tuple[int, ...]] = None,
                      knob: Optional[str] = None) -> str:
    """Resolve the per-bucket algorithm.

    ``requested`` is the user/config choice (one of :data:`ALGORITHMS`);
    ``nbytes`` the static bucket size; ``reducible`` whether the op has
    an RS+AG decomposition at all (Sum/Average do; Min/Max/Product/
    Adasum pass through to their existing lowerings — requesting
    ``rs_ag`` for an Adasum allreduce is a no-op by design, so one
    training script can set a global algorithm without branching on op).

    ``auto`` on the exact wire (``wire`` ``None``/``"fp32"``/``"bf16"``)
    is ``psum`` whatever the size, the world or the torus: XLA's own
    all-reduce over the whole axis. No decomposition has been seen to
    win on links (see the note at :data:`RS_AG_MIN_BYTES`), and a rule
    for a fabric nobody timed would be a guess.

    ``wire`` is the default wire precision (``HOROVOD_ALLREDUCE_WIRE``):
    when ``"int8"``/``"fp8"``, the payload is quantized inside an RS+AG
    decomposition, so ``auto`` picks one by size — small buckets keep
    the exact one-op psum and only bandwidth-bound buckets pay the
    quantize/dequantize math. An explicit ``requested`` algorithm always
    wins over the wire default.

    ``topology`` is the detected torus dims (``core.topology()``): with
    >= 2 non-degenerate dims, ``auto``'s quantized picks take the
    multi-phase ``_2d`` lowerings, whose phases ride shorter sub-rings.
    Explicit requests degrade rather than fail when the fabric cannot
    carry them — ``*_2d`` on a 1-D ring runs the 1-D base (same wire),
    ``swing`` on a non-power-of-two world runs psum — so one launch
    script can pin an algorithm across differently-shaped slices.
    ``knob`` optionally names the config surface that produced
    ``requested`` for the rejection message.
    """
    if requested not in ALGORITHMS:
        _reject_algorithm(requested, knob)
    if not reducible or world <= 1:
        return "psum"
    ndims = _torus_ndims(topology)
    if requested != "auto":
        if requested == "swing" and (world & (world - 1)):
            log.debug("swing needs a power-of-two world (have %d); "
                      "falling back to psum", world)
            return "psum"
        base, qw = parse_algorithm(requested)
        if base.endswith("_2d") and ndims < 2:
            return compose_algorithm(base[:-3], qw)
        return requested
    if wire not in QUANT_WIRES:
        return "psum"
    if nbytes >= CHUNKED_MIN_BYTES:
        return compose_algorithm(
            "chunked_rs_ag_2d" if ndims >= 2 else "chunked_rs_ag", wire)
    if nbytes >= RS_AG_MIN_BYTES:
        return compose_algorithm(
            "rs_ag_2d" if ndims >= 2 else "rs_ag", wire)
    return "psum"


def _split_sizes(m: int, n: int, chunks: int) -> Tuple[int, int]:
    """(per_chunk, n_chunks) for an m-element buffer reduced over n
    devices: every chunk must be a multiple of n (psum_scatter tiles
    dim 0 across the axis) and empty all-padding chunks are clamped
    away."""
    chunks = max(1, int(chunks))
    chunks = min(chunks, max(1, -(-m // n)))      # no all-padding chunks
    per = -(-m // chunks)                         # ceil split
    per = -(-per // n) * n                        # round up to n-multiple
    # per * chunks >= m by construction
    return per, chunks


def wire_bytes(nelems: int, wire: str, elem_bytes: int = 4) -> int:
    """Bytes a bucket of ``nelems`` elements puts on the wire per ring
    traversal under ``wire`` (one of :data:`WIRES`, or a dtype-ish label
    like ``"fp16"``). Quantized wires count the 1-byte payload plus the
    fp32 per-block scales that ride alongside; the constant ring factor
    2(n-1)/n is identical across formats and deliberately excluded, so
    ratios between formats are exact."""
    from horovod_tpu.ops.quantized import wire_overhead_bytes
    if wire in QUANT_WIRES:
        return nelems + wire_overhead_bytes(nelems)
    if wire == "bf16" or wire == "fp16":
        return 2 * nelems
    return elem_bytes * nelems


def wire_bytes_by_phase(base: str, nelems: int, wire: str, world: int,
                        dims: Optional[Tuple[int, ...]] = None,
                        elem_bytes: int = 4) -> dict:
    """Per-leg wire bytes for one traversal of an ``nelems`` bucket under
    ``base`` (an exchange-structure name from :func:`parse_algorithm` —
    wire suffix already stripped). Returns ``{phase_label: bytes}``.

    This is the multi-leg accounting :func:`wire_bytes` alone cannot
    express: an RS+AG decomposition puts the bucket on the wire TWICE
    (reduce-scatter leg, then all-gather leg — and a quantized wire
    carries per-block scales on BOTH, since the all-gather re-quantizes),
    a ``_2d`` lowering runs one RS and one AG leg per torus dim with the
    payload shrinking by that dim's extent each phase, and ``swing``
    halves its payload every exchange step (totalling ~one traversal per
    direction). ``psum`` is a single fused collective: one ``all`` leg.
    Ring factors (d-1)/d are excluded per leg, same normalization as
    :func:`wire_bytes`.
    """
    if base in ("psum", "auto"):
        return {"all": wire_bytes(nelems, wire, elem_bytes)}
    if base == "swing":
        # sum over steps of nelems/2^(s+1) = nelems*(n-1)/n per direction
        c = -(-nelems // max(world, 1))
        legs = c * max(world - 1, 1)
        return {"rs": wire_bytes(legs, wire, elem_bytes),
                "ag": wire_bytes(legs, wire, elem_bytes)}
    if base.endswith("_2d"):
        ds = tuple(int(d) for d in (dims or ()) if int(d) > 1)
        if len(ds) < 2:
            ds = (world,)     # degraded to the 1-D ring: one RS + one AG
        sizes, m = [], nelems
        for d in ds:                 # payload entering phase j
            sizes.append(m)
            m = -(-m // d)
        out = {f"rs_d{j}": wire_bytes(sizes[j], wire, elem_bytes)
               for j in range(len(ds))}
        for j in range(len(ds) - 1, -1, -1):
            out[f"ag_d{j}"] = wire_bytes(sizes[j], wire, elem_bytes)
        return out
    # rs_ag / chunked_rs_ag: full payload (+scales) on each of two legs
    return {"rs": wire_bytes(nelems, wire, elem_bytes),
            "ag": wire_bytes(nelems, wire, elem_bytes)}


def rs_ag_psum(x: jnp.ndarray, axis: str, world: int) -> jnp.ndarray:
    """Bandwidth-optimal sum-allreduce of a 1-D buffer: reduce-scatter
    then all-gather over ``axis`` (2(n-1)/n bytes per device on a ring
    vs the fused psum's scheduler choice). Shape-preserving; padding is
    internal."""
    return chunked_rs_ag_psum(x, axis, world, chunks=1)


def chunked_rs_ag_psum(x: jnp.ndarray, axis: str, world: int,
                       chunks: int = DEFAULT_CHUNKS,
                       wire: Optional[str] = None,
                       mean_k: Optional[float] = None) -> jnp.ndarray:
    """Sum-allreduce a 1-D buffer as ``chunks`` pipelined RS+AG pairs.

    The chunk reduce-scatters are chained with
    ``lax.optimization_barrier`` so their issue order is pinned
    (chunk i+1's RS cannot be hoisted before chunk i's): XLA is then
    free to overlap chunk i's all-gather — and, under the latency-hiding
    scheduler, surrounding compute — with chunk i+1's reduce-scatter.
    Numerically this is the same per-element sum of ``world``
    contributions as one psum (each element is reduced exactly once, by
    one scatter shard).

    ``wire="int8"``/``"fp8"`` runs the same pipeline with an EQuARX-style
    quantized wire (``ops/quantized.py`` block scaling): each chunk is
    quantized per destination shard (fresh per-block scales), exchanged
    with ``all_to_all`` (the reduce-scatter leg — 1-byte payload + fp32
    scales on the wire), dequantized and reduced **exactly in fp32** at
    the owning shard, then re-quantized for the ``all_gather`` leg. The
    input must be fp32 on this path (callers cast); ``mean_k`` divides
    the reduced partial *before* re-quantization (Average in a subset of
    ``k`` members) so the second quantization grid matches the returned
    magnitudes.
    """
    if x.ndim != 1:
        raise ValueError(f"rs+ag operates on 1-D fusion buffers, got "
                         f"shape {x.shape}")
    if mean_k is not None and wire is None:
        raise ValueError("mean_k applies to the quantized wire path only")
    if wire is not None:
        return _chunked_rs_ag_quantized(x, axis, world, chunks, wire,
                                        mean_k)
    m = x.shape[0]
    if m == 0 or world <= 1:
        return x
    per, chunks = _split_sizes(m, world, chunks)
    total = per * chunks
    if total != m:
        x = jnp.concatenate(
            [x, jnp.zeros((total - m,), x.dtype)])
    elem = jnp.dtype(x.dtype).itemsize
    for i in range(chunks):
        _metrics.histogram("allreduce_chunk_bytes",
                           buckets=_metrics.SIZE_BUCKETS).observe(per * elem)
    # Program-registry entry (profiler.py): fires once per compiled
    # lowering — the doctor reads chunk geometry from here when judging
    # overlap efficiency against the knobs actually in effect.
    try:
        from horovod_tpu import profiler as _profiler
        _profiler.count_trace("overlap:chunked_rs_ag", chunks=chunks,
                              chunk_bytes=per * elem,
                              buffer_bytes=m * elem)
    except Exception:
        pass
    scattered = []
    prev = None
    for i in range(chunks):
        piece = lax.slice(x, (i * per,), ((i + 1) * per,))
        if prev is not None:
            # Pin issue order: chunk i's RS result gates chunk i+1's RS
            # input. The barrier is ordering-only — values pass through
            # untouched — but it stops XLA from fusing every chunk into
            # one monolithic collective, which is the whole pipeline.
            piece, prev = lax.optimization_barrier((piece, prev))
        s = lax.psum_scatter(piece, axis, scatter_dimension=0, tiled=True)
        scattered.append(s)
        prev = s
    gathered = [lax.all_gather(s, axis, tiled=True) for s in scattered]
    out = gathered[0] if chunks == 1 else jnp.concatenate(gathered)
    return out if total == m else lax.slice(out, (0,), (m,))


def _chunked_rs_ag_quantized(x: jnp.ndarray, axis: str, world: int,
                             chunks: int, wire: str,
                             mean_k: Optional[float]) -> jnp.ndarray:
    """Quantized-wire body of :func:`chunked_rs_ag_psum` (two-phase
    exchange per pipelined chunk)."""
    from horovod_tpu.ops.quantized import (BLOCK, WIRE_FORMATS,
                                           dequantize_blocks,
                                           quantize_blocks)
    if wire not in WIRE_FORMATS:
        raise ValueError(f"unknown quantized wire {wire!r}; expected one "
                         f"of {WIRE_FORMATS}")
    if x.dtype != jnp.float32:
        raise ValueError("quantized rs+ag reduces in fp32; cast the "
                         f"buffer first (got {x.dtype})")
    m = x.shape[0]
    if m == 0 or world <= 1:
        if mean_k is not None and world <= 1 and m:
            return x / jnp.float32(mean_k)
        return x
    # Chunk geometry: every chunk splits into one BLOCK-aligned row per
    # destination shard, so per must be a multiple of world * BLOCK.
    per, chunks = _split_sizes(m, world * BLOCK, chunks)
    total = per * chunks
    if total != m:
        x = jnp.concatenate([x, jnp.zeros((total - m,), x.dtype)])
    c = per // world                      # owned sub-chunk per device
    wbytes = wire_bytes(per, wire)
    for i in range(chunks):
        _metrics.histogram("allreduce_chunk_bytes",
                           buckets=_metrics.SIZE_BUCKETS).observe(wbytes)
    try:
        from horovod_tpu import profiler as _profiler
        _profiler.count_trace(f"overlap:chunked_rs_ag_{wire}",
                              chunks=chunks, chunk_wire_bytes=wbytes,
                              buffer_bytes=m * 4)
    except Exception:
        pass
    scattered = []
    prev = None
    for i in range(chunks):
        piece = lax.slice(x, (i * per,), ((i + 1) * per,))
        if prev is not None:
            # Same issue-order pinning as the exact pipeline: chunk i's
            # reduced partial gates chunk i+1's quantization, so XLA can
            # overlap chunk i's all-gather with chunk i+1's exchange.
            piece, prev = lax.optimization_barrier((piece, prev))
        rows = piece.reshape(world, c)    # row j -> destination shard j
        q, scale = quantize_blocks(rows, wire)
        q_recv = lax.all_to_all(q, axis, split_axis=0, concat_axis=0)
        s_recv = lax.all_to_all(scale, axis, split_axis=0, concat_axis=0)
        part = jnp.sum(dequantize_blocks(q_recv, s_recv), axis=0)  # (c,)
        if mean_k is not None:
            part = part / jnp.float32(mean_k)
        scattered.append(part)
        prev = part
    gathered = []
    for part in scattered:
        q2, s2 = quantize_blocks(part, wire)
        qg = lax.all_gather(q2, axis)                    # (world, c)
        sg = lax.all_gather(s2, axis)
        gathered.append(dequantize_blocks(qg, sg).reshape(world * c))
    out = gathered[0] if chunks == 1 else jnp.concatenate(gathered)
    return out if total == m else lax.slice(out, (0,), (m,))


# ---------------------------------------------------------------------------
# torus-native multi-phase RS+AG (the `_2d` family)
# ---------------------------------------------------------------------------

def _phase_groups(dims: Tuple[int, ...]):
    """Cached per-dim ``axis_index_groups`` for a row-major torus."""
    from horovod_tpu.parallel.mesh import torus_groups
    return torus_groups(dims)


def rs_ag_2d_psum(x: jnp.ndarray, axis: str, world: int,
                  dims: Tuple[int, ...],
                  wire: Optional[str] = None,
                  mean_k: Optional[float] = None) -> jnp.ndarray:
    """Single-chunk :func:`chunked_rs_ag_2d_psum`."""
    return chunked_rs_ag_2d_psum(x, axis, world, dims, chunks=1,
                                 wire=wire, mean_k=mean_k)


def chunked_rs_ag_2d_psum(x: jnp.ndarray, axis: str, world: int,
                          dims: Tuple[int, ...],
                          chunks: int = DEFAULT_CHUNKS,
                          wire: Optional[str] = None,
                          mean_k: Optional[float] = None) -> jnp.ndarray:
    """Sum-allreduce a 1-D buffer as a multi-phase torus reduction
    (PAPERS.md "Highly Available Data Parallel ML training on Mesh
    Networks", arxiv 2011.03605), pipelined over ``chunks``.

    The flat rank axis is laid out row-major over the torus ``dims``;
    each phase is a sub-axis collective expressed with
    ``axis_index_groups`` (lines along one torus dim — a full equal-size
    partition of the axis). Reduce-scatter runs along dim 0, then dim 1,
    ... — each phase over a ``d``-long sub-ring carrying ``1/prod(d_<j)``
    of the bucket — and the all-gathers run back in reverse order, each
    exactly inverting its scatter, so the result equals one full-axis
    RS+AG while every wire leg rides a shorter ring of the physical
    torus.

    ``wire="int8"``/``"fp8"`` quantizes per phase: each RS leg exchanges
    freshly block-quantized partials (``all_to_all`` + exact fp32
    reduction at the owner, per phase), and after the final reduction
    the owned sub-block is re-quantized ONCE — the all-gather legs relay
    those same wire bytes (payload + scales) back through every phase,
    so all ranks dequantize identical bytes and the result is
    bit-identical across ranks. ``mean_k`` divides before the
    re-quantization, as in the 1-D quantized path.
    """
    if x.ndim != 1:
        raise ValueError(f"rs+ag operates on 1-D fusion buffers, got "
                         f"shape {x.shape}")
    dims = tuple(int(d) for d in dims if int(d) > 1)
    prod = 1
    for d in dims:
        prod *= d
    if len(dims) >= 2 and prod != world:
        raise ValueError(
            f"torus dims {dims} describe {prod} devices but the axis has "
            f"{world}")
    if len(dims) < 2:
        # degenerate fabric: the 1-D pipeline is the same exchange
        return chunked_rs_ag_psum(x, axis, world, chunks=chunks,
                                  wire=wire, mean_k=mean_k)
    if mean_k is not None and wire is None:
        raise ValueError("mean_k applies to the quantized wire path only")
    m = x.shape[0]
    if m == 0 or world <= 1:
        return x
    groups = _phase_groups(dims)
    if wire is not None:
        return _chunked_rs_ag_2d_quantized(x, axis, world, dims, groups,
                                           chunks, wire, mean_k)
    per, chunks = _split_sizes(m, world, chunks)
    total = per * chunks
    if total != m:
        x = jnp.concatenate([x, jnp.zeros((total - m,), x.dtype)])
    elem = jnp.dtype(x.dtype).itemsize
    for i in range(chunks):
        _metrics.histogram("allreduce_chunk_bytes",
                           buckets=_metrics.SIZE_BUCKETS).observe(per * elem)
    try:
        from horovod_tpu import profiler as _profiler
        _profiler.count_trace("overlap:chunked_rs_ag_2d", chunks=chunks,
                              chunk_bytes=per * elem, buffer_bytes=m * elem,
                              topology="x".join(map(str, dims)))
    except Exception:
        pass
    scattered = []
    prev = None
    for i in range(chunks):
        piece = lax.slice(x, (i * per,), ((i + 1) * per,))
        if prev is not None:
            # Same issue-order pinning as the 1-D pipeline.
            piece, prev = lax.optimization_barrier((piece, prev))
        cur = piece
        for j in range(len(dims)):
            cur = lax.psum_scatter(cur, axis, scatter_dimension=0,
                                   tiled=True, axis_index_groups=groups[j])
        scattered.append(cur)
        prev = cur
    gathered = []
    for cur in scattered:
        for j in range(len(dims) - 1, -1, -1):
            cur = lax.all_gather(cur, axis, tiled=True,
                                 axis_index_groups=groups[j])
        gathered.append(cur)
    out = gathered[0] if chunks == 1 else jnp.concatenate(gathered)
    return out if total == m else lax.slice(out, (0,), (m,))


def _chunked_rs_ag_2d_quantized(x: jnp.ndarray, axis: str, world: int,
                                dims: Tuple[int, ...], groups,
                                chunks: int, wire: str,
                                mean_k: Optional[float]) -> jnp.ndarray:
    """Per-phase quantized body of :func:`chunked_rs_ag_2d_psum`."""
    from horovod_tpu.ops.quantized import (BLOCK, WIRE_FORMATS,
                                           dequantize_blocks,
                                           quantize_blocks)
    if wire not in WIRE_FORMATS:
        raise ValueError(f"unknown quantized wire {wire!r}; expected one "
                         f"of {WIRE_FORMATS}")
    if x.dtype != jnp.float32:
        raise ValueError("quantized rs+ag reduces in fp32; cast the "
                         f"buffer first (got {x.dtype})")
    m = x.shape[0]
    if m == 0 or world <= 1:
        if mean_k is not None and world <= 1 and m:
            return x / jnp.float32(mean_k)
        return x
    # Every phase splits the current partial into one BLOCK-aligned row
    # per sub-ring member; a per-chunk size of world*BLOCK keeps every
    # phase's rows BLOCK-multiples (phase j rows are per/prod(d_<=j)).
    per, chunks = _split_sizes(m, world * BLOCK, chunks)
    total = per * chunks
    if total != m:
        x = jnp.concatenate([x, jnp.zeros((total - m,), x.dtype)])
    wbytes = sum(wire_bytes_by_phase("rs_ag_2d", per, wire, world,
                                     dims=dims).values())
    for i in range(chunks):
        _metrics.histogram("allreduce_chunk_bytes",
                           buckets=_metrics.SIZE_BUCKETS).observe(wbytes)
    try:
        from horovod_tpu import profiler as _profiler
        _profiler.count_trace(f"overlap:chunked_rs_ag_2d_{wire}",
                              chunks=chunks, chunk_wire_bytes=wbytes,
                              buffer_bytes=m * 4,
                              topology="x".join(map(str, dims)))
    except Exception:
        pass
    scattered = []
    prev = None
    for i in range(chunks):
        piece = lax.slice(x, (i * per,), ((i + 1) * per,))
        if prev is not None:
            piece, prev = lax.optimization_barrier((piece, prev))
        cur = piece
        for j, d in enumerate(dims):
            rows = cur.reshape(d, cur.shape[0] // d)
            q, scale = quantize_blocks(rows, wire)   # fresh per-phase scales
            q_recv = lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                                    axis_index_groups=groups[j])
            s_recv = lax.all_to_all(scale, axis, split_axis=0,
                                    concat_axis=0,
                                    axis_index_groups=groups[j])
            cur = jnp.sum(dequantize_blocks(q_recv, s_recv), axis=0)
        if mean_k is not None:
            cur = cur / jnp.float32(mean_k)
        scattered.append(cur)
        prev = cur
    gathered = []
    for part in scattered:
        # One re-quantization at the owning shard; the gather legs relay
        # the same payload+scales through every phase, so every rank
        # dequantizes identical wire bytes.
        q2, s2 = quantize_blocks(part, wire)
        for j in range(len(dims) - 1, -1, -1):
            q2 = lax.all_gather(q2, axis, tiled=True,
                                axis_index_groups=groups[j])
            s2 = lax.all_gather(s2, axis, tiled=True,
                                axis_index_groups=groups[j])
        gathered.append(dequantize_blocks(q2, s2))
    out = gathered[0] if chunks == 1 else jnp.concatenate(gathered)
    return out if total == m else lax.slice(out, (0,), (m,))


# ---------------------------------------------------------------------------
# Swing: distance-halving schedule for latency-bound buckets
# ---------------------------------------------------------------------------

def _swing_schedule(world: int):
    """Static per-step tables of the Swing allreduce (PAPERS.md arxiv
    2401.09356) on ``world`` (power of two) ranks.

    Step ``s`` pairs rank ``r`` with ``r +/- rho_s (mod n)`` where
    ``rho_s = (1-(-2)^(s+1))/3`` (distances 1, 1, 3, 5, 11, ... — on a
    physical ring each hop direction alternates, which is what lets
    Swing short-cut the torus). The pairing is an involution at every
    step; block responsibilities are built BACKWARD from the final
    owner-block assignment ``b(r) = r``:

        T_k(r) = {r};   T_s(r) = T_{s+1}(r) | T_{s+1}(partner_s(r))

    so after RS step s, rank r holds partial sums for exactly the blocks
    its remaining steps still feed — and the union is checked disjoint
    (asserted), which is the property that makes every block's sum a
    single deterministic association tree at one owner: the all-gather
    phase then broadcasts the owner's bytes verbatim, so results are
    bit-identical across ranks.

    Returns ``(k, perms, keep, send)``: ``k`` steps; ``perms[s]`` the
    ppermute pairing; ``keep[s]``/``send[s]`` int32 tables of shape
    ``(n, n/2^(s+1))`` — the (sorted) block rows rank r keeps/packs at
    RS step s. The AG phase reuses them mirrored (send along ``keep``,
    store into ``send``).
    """
    return _swing_schedule_cached(int(world))


@functools.lru_cache(maxsize=None)
def _swing_schedule_cached(n: int):
    k = n.bit_length() - 1
    if n < 2 or (1 << k) != n:
        raise ValueError(f"swing requires a power-of-two world, got {n}")
    partners = []
    for s in range(k):
        rho = (1 - (-2) ** (s + 1)) // 3
        p = [(r + rho) % n if r % 2 == 0 else (r - rho) % n
             for r in range(n)]
        for r in range(n):
            assert p[p[r]] == r and p[r] != r, (s, r)
        partners.append(p)
    T = [[None] * n for _ in range(k + 1)]
    for r in range(n):
        T[k][r] = {r}
    for s in range(k - 1, -1, -1):
        for r in range(n):
            mine, other = T[s + 1][r], T[s + 1][partners[s][r]]
            assert not (mine & other), \
                f"swing schedule overlap at step {s}, rank {r}"
            T[s][r] = mine | other
    for r in range(n):
        assert T[0][r] == set(range(n))
    keep = tuple(np.array([sorted(T[s + 1][r]) for r in range(n)],
                          np.int32) for s in range(k))
    send = tuple(np.array([sorted(T[s + 1][partners[s][r]])
                           for r in range(n)], np.int32)
                 for s in range(k))
    perms = tuple(tuple((r, partners[s][r]) for r in range(n))
                  for s in range(k))
    return k, perms, keep, send


def swing_psum(x: jnp.ndarray, axis: str, world: int) -> jnp.ndarray:
    """Sum-allreduce a 1-D buffer with the Swing distance-halving
    schedule: log2(n) pairwise exchange steps per direction (vs a ring's
    n-1) at the same ~2m total wire bytes — the latency-bound
    counterpart of :func:`rs_ag_psum`. Exact wire only; ``world`` must
    be a power of two (:func:`resolve_algorithm` falls back to psum
    otherwise). Bit-identical across ranks: each block is reduced by one
    deterministic association tree at its owner, then broadcast
    verbatim.
    """
    if x.ndim != 1:
        raise ValueError(f"swing operates on 1-D fusion buffers, got "
                         f"shape {x.shape}")
    m = x.shape[0]
    if m == 0 or world <= 1:
        return x
    k, perms, keep, send = _swing_schedule(world)
    c = -(-m // world)
    total = c * world
    if total != m:
        x = jnp.concatenate([x, jnp.zeros((total - m,), x.dtype)])
    elem = jnp.dtype(x.dtype).itemsize
    _metrics.histogram("allreduce_chunk_bytes",
                       buckets=_metrics.SIZE_BUCKETS).observe(total * elem)
    try:
        from horovod_tpu import profiler as _profiler
        _profiler.count_trace("overlap:swing", steps=2 * k,
                              block_bytes=c * elem, buffer_bytes=m * elem)
    except Exception:
        pass
    blocks = x.reshape(world, c)
    ridx = lax.axis_index(axis)
    # Reduce-scatter phase: send the partials my partner's future cone
    # needs, fold the received ones into mine. Rows already sent go
    # stale but are never read again (future keep/send sets shrink).
    for s in range(k):
        srows = jnp.take(jnp.asarray(send[s]), ridx, axis=0)
        krows = jnp.take(jnp.asarray(keep[s]), ridx, axis=0)
        payload = jnp.take(blocks, srows, axis=0)
        recv = lax.ppermute(payload, axis, perm=perms[s])
        blocks = blocks.at[krows].add(recv)
    # All-gather phase, mirrored: relay the final blocks I hold, store
    # the partner's verbatim.
    for s in range(k - 1, -1, -1):
        krows = jnp.take(jnp.asarray(keep[s]), ridx, axis=0)
        prows = jnp.take(jnp.asarray(send[s]), ridx, axis=0)
        payload = jnp.take(blocks, krows, axis=0)
        recv = lax.ppermute(payload, axis, perm=perms[s])
        blocks = blocks.at[prows].set(recv)
    out = blocks.reshape(total)
    return out if total == m else lax.slice(out, (0,), (m,))


# ---------------------------------------------------------------------------
# backward taps: issue collectives inside the backward pass
# ---------------------------------------------------------------------------

def make_grad_sync_tap(**allreduce_kwargs) -> Callable[[Any], Any]:
    """Build a ``custom_vjp`` identity whose backward rule allreduces the
    incoming cotangent (``hvd.allreduce(**allreduce_kwargs)``).

    Apply it to a parameter (sub)tree *before* the forward uses it: the
    forward is untouched, and during backward the group's gradient is
    synchronized the moment it is produced — for the last-used group
    that is long before the first layers finish their backward, which is
    the latency-hiding window the reference chased with ready-ordering.
    Outside an SPMD context the tap is a full identity (mirrors
    ``allreduce_gradients``'s jit-auto-sharding behaviour).
    """

    @jax.custom_vjp
    def tap(tree):
        return tree

    def fwd(tree):
        return tree, None

    def bwd(_, ct):
        from horovod_tpu import collective as C
        from horovod_tpu import core
        if not core.in_spmd_context():
            return (ct,)
        return (C.allreduce(ct, **allreduce_kwargs),)

    tap.defvjp(fwd, bwd)
    return tap


def tap_params(params: Any, **allreduce_kwargs) -> Any:
    """Tap every top-level group of ``params`` with its own gradient-sync
    identity (:func:`make_grad_sync_tap`).

    One tap per top-level child (one for a leaf/opaque tree) means one
    independent backward collective per group, issued in reverse
    production order by the backward pass itself — no end-of-backward
    barrier. Used by ``hvd.grad(..., overlap=True)``.
    """
    if isinstance(params, dict):
        return {k: make_grad_sync_tap(**allreduce_kwargs)(v)
                for k, v in params.items()}
    if isinstance(params, (list, tuple)):
        out = [make_grad_sync_tap(**allreduce_kwargs)(v) for v in params]
        return type(params)(out)
    return make_grad_sync_tap(**allreduce_kwargs)(params)
