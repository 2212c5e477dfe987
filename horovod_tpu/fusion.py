"""Tensor fusion: pack many small tensors into few large buffers.

Rebuild of upstream ``horovod/common/fusion_buffer_manager.cc`` +
``horovod/common/controller.cc`` cycle-time batching. The reference copies
pending tensors into a persistent 64 MB fusion buffer so one NCCL allreduce
replaces hundreds of small ones.

On TPU the motivation survives (per-collective latency on ICI, and XLA
schedules one big psum better than many tiny ones) but the mechanism is
functional: leaves are raveled and concatenated into per-dtype buckets of at
most ``threshold_bytes``; after the collective the buckets are split and
reshaped back. Everything happens inside jit — XLA turns the concat/split into
cheap copies and the persistent-buffer bookkeeping of the reference collapses
into compile-time layout.

Two details matter for the overlapped RS+AG pipeline (``overlap.py``):

* a leaf **larger** than the threshold no longer rides one giant bucket —
  it is split into tile-aligned sub-chunks of at most ``threshold_bytes``
  (each a bucket), so per-bucket algorithm selection and chunked RS+AG
  apply to giant embedding tables exactly like to everything else;
* ``unpack`` uses **static** ``lax.slice`` (offsets are python ints), so
  XLA constant-folds the split instead of carrying dynamic-slice ops.
"""

from __future__ import annotations

from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import metrics as _metrics
from horovod_tpu import tracing as _tracing

__all__ = ["DEFAULT_FUSION_THRESHOLD_BYTES", "fuse", "unfuse", "fused_apply"]

# Matches HOROVOD_FUSION_THRESHOLD default (64 MB).
DEFAULT_FUSION_THRESHOLD_BYTES = 64 * 1024 * 1024


def _nbytes(leaf) -> int:
    return leaf.size * jnp.dtype(leaf.dtype).itemsize


# Capacity accounting pads each tensor to the TPU lane-tile stride
# (128 lanes x 4 B), matching how the reference pads entries in its fusion
# buffer; bucket *contents* are still tightly concatenated.
FUSION_ALIGN_BYTES = 512


def _plan_buckets(sizes: Sequence[int], threshold_bytes: int) -> List[int]:
    """Bucket index per tensor: native planner if built (first use may build
    the .so with make, a one-time ~2s cost), else same greedy in Python. A
    tensor larger than the threshold gets its own bucket."""
    from horovod_tpu import native
    assignment = native.fusion_plan(list(sizes), threshold_bytes,
                                    align_bytes=FUSION_ALIGN_BYTES)
    if assignment is not None:
        return assignment
    out, used, bucket = [], 0, -1
    for sz in sizes:
        sz = -(-sz // FUSION_ALIGN_BYTES) * FUSION_ALIGN_BYTES
        if bucket < 0 or used + sz > threshold_bytes:
            bucket += 1
            used = 0
        out.append(bucket)
        used += sz
    return out


def _split_oversize(leaves, threshold_bytes: int):
    """Segment list per leaf: ``[(leaf_idx, start_elem, n_elem), ...]``.

    Leaves within the threshold are one whole-leaf segment. An oversize
    leaf is cut into sub-chunks of at most ``threshold_bytes``, each
    aligned to the fusion tile stride, so every downstream bucket — and
    therefore every collective the buckets feed — stays within the
    threshold the user tuned.
    """
    segments = []
    split_leaves = set()
    for i, leaf in enumerate(leaves):
        itemsize = jnp.dtype(leaf.dtype).itemsize
        if _nbytes(leaf) <= threshold_bytes or leaf.size <= 1:
            segments.append((i, 0, leaf.size))
            continue
        split_leaves.add(i)
        align_elems = max(1, FUSION_ALIGN_BYTES // itemsize)
        chunk = max(align_elems,
                    (threshold_bytes // itemsize) // align_elems
                    * align_elems)
        off = 0
        while off < leaf.size:
            n = min(chunk, leaf.size - off)
            segments.append((i, off, n))
            off += n
    return segments, split_leaves


def fuse(leaves: Sequence[Any],
         threshold_bytes: int = DEFAULT_FUSION_THRESHOLD_BYTES,
         pad_elems: int = 1
         ) -> Tuple[List[jnp.ndarray], Callable[[List[jnp.ndarray]], List[Any]]]:
    """Pack ``leaves`` into fusion buckets.

    Returns ``(buckets, unpack)`` where ``buckets`` is a list of 1-D arrays
    (one per dtype-bucket, each at most ``threshold_bytes`` — oversize
    leaves are split across several) and ``unpack`` restores the original
    list of leaves from same-shaped buckets.

    ``pad_elems > 1`` zero-pads every packed segment to a multiple of
    that many *elements* inside its bucket (``unpack`` slices the real
    spans back out). The quantized-wire allreduce passes the quantization
    block size here so per-block scales never straddle two leaves — a
    large-magnitude layer sharing a bucket with a small-magnitude one
    cannot flush the latter to zero through a shared scale.
    """
    leaves = [jnp.asarray(x) for x in leaves]
    # Stable greedy packing, grouped by dtype (a fused buffer must be
    # homogeneous, as in the reference where the buffer is typed). The
    # bucket assignment itself runs in the native planner when available
    # (cpp/hvdtpu_core.cpp:hvd_fusion_plan), Python fallback otherwise.
    segments, split_leaves = _split_oversize(leaves, threshold_bytes)
    itemsize = [jnp.dtype(l.dtype).itemsize for l in leaves]
    pad_elems = max(1, int(pad_elems))

    def _padded_len(s: int) -> int:
        n = segments[s][2]
        return -(-n // pad_elems) * pad_elems

    by_dtype: dict = {}                 # dtype -> segment indices (stable)
    for s, (i, _, _) in enumerate(segments):
        by_dtype.setdefault(jnp.dtype(leaves[i].dtype), []).append(s)

    plan: List[List[int]] = []          # bucket -> segment indices
    causes: List[str] = []              # why each bucket was closed
    for segs in by_dtype.values():
        sizes = [_padded_len(s) * itemsize[segments[s][0]] for s in segs]
        assignment = _plan_buckets(sizes, threshold_bytes)
        groups: dict = {}
        for s, b in zip(segs, assignment):
            groups.setdefault(b, []).append(s)
        ordered = [groups[b] for b in sorted(groups)]
        plan.extend(ordered)
        for j, g in enumerate(ordered):
            if all(segments[s][0] in split_leaves for s in g):
                # Bucket exists only because a leaf beat the cap and was
                # split; a MIXED bucket (split tail + ordinary leaves)
                # closed for the usual reasons and is counted as such.
                causes.append("oversize_leaf")
            elif j < len(ordered) - 1:
                causes.append("capacity")        # next leaf would overflow
            else:
                causes.append("end_of_group")    # dtype group / tree end

    # Observability (trace-time: fuse runs under jit, so these count per
    # COMPILATION, not per step — sizes are static python ints, never
    # tracers). Fill ratio is bytes packed over the threshold; oversize
    # leaves are split, so it is now always <= 1.0 + one tile stride.
    _metrics.counter("fusion_tensors_total").inc(len(leaves))
    _metrics.counter("fusion_buckets_total").inc(len(plan))
    # Span context of the collective whose tree is being fused (set by
    # collective.py around eager dispatch and traced lowerings): flush
    # events carry the owning op-id so a merged trace can tie each fusion
    # bucket back to the collective it fed.
    span = _tracing.current_span()
    for bucket_i, (segs, cause) in enumerate(zip(plan, causes)):
        b_bytes = sum(segments[s][2] * itemsize[segments[s][0]]
                      for s in segs)
        _metrics.counter("fusion_flush_total", cause=cause).inc()
        _metrics.histogram("fusion_fill_ratio",
                           buckets=_metrics.RATIO_BUCKETS).observe(
            b_bytes / max(threshold_bytes, 1))
        _metrics.histogram("fusion_bucket_bytes",
                           buckets=_metrics.SIZE_BUCKETS).observe(b_bytes)
        if span is not None:
            member = sorted({segments[s][0] for s in segs})
            _metrics._timeline_marker(
                "fusion_flush", category="fusion", op_id=span.op_id,
                tensor=span.tensor, bucket=bucket_i,
                member_leaves=member, bytes=b_bytes, cause=cause)

    def _segment_slice(s: int) -> jnp.ndarray:
        i, start, n = segments[s]
        flat = leaves[i].ravel()
        if not (start == 0 and n == flat.shape[0]):
            flat = lax.slice(flat, (start,), (start + n,))
        padded = _padded_len(s)
        if padded != n:
            flat = jnp.concatenate(
                [flat, jnp.zeros((padded - n,), flat.dtype)])
        return flat

    with _tracing.scope("hvd/fusion/pack"):
        buckets = [
            _segment_slice(segs[0]) if len(segs) == 1
            else jnp.concatenate([_segment_slice(s) for s in segs])
            for segs in plan
        ]
    shapes = [leaves[i].shape for i in range(len(leaves))]

    @_tracing.scope("hvd/fusion/unpack")
    def unpack(new_buckets: List[jnp.ndarray]) -> List[Any]:
        pieces: dict = {}               # leaf -> [(start, piece)]
        for b, segs in enumerate(plan):
            buf = new_buckets[b]
            off = 0
            for s in segs:
                i, start, n = segments[s]
                # Static slice: offsets are python ints, so XLA
                # constant-folds the split (no dynamic-slice ops).
                # Padded tail elements (pad_elems alignment) are skipped.
                piece = lax.slice(buf, (off,), (off + n,))
                pieces.setdefault(i, []).append((start, piece))
                off += _padded_len(s)
        out: List[Any] = [None] * len(leaves)
        for i, parts in pieces.items():
            parts.sort(key=lambda p: p[0])
            flat = parts[0][1] if len(parts) == 1 else \
                jnp.concatenate([p for _, p in parts])
            out[i] = flat.reshape(shapes[i])
        return out

    return buckets, unpack


def unfuse(buckets, unpack):
    return unpack(buckets)


def fused_apply(fn: Callable[[jnp.ndarray], jnp.ndarray], tree: Any,
                threshold_bytes: int = DEFAULT_FUSION_THRESHOLD_BYTES,
                reverse: bool = False, pin_order: bool = False,
                pad_elems: int = 1) -> Any:
    """Apply a 1-D-buffer collective ``fn`` to every leaf of ``tree`` through
    fusion buckets, preserving structure.

    ``reverse=True`` issues the per-bucket collectives in reverse bucket
    order — the gradient-overlap convention: backward produces the LAST
    parameters' gradients first, so their bucket's collective should be
    first in line. ``pin_order=True`` additionally chains consecutive
    collectives through ``lax.optimization_barrier`` so the issue order
    survives scheduling — each collective still depends only on its own
    bucket's data plus the previous collective's completion, leaving XLA
    free to overlap it with unrelated compute. ``pad_elems`` forwards to
    :func:`fuse` (quantization-block alignment of leaves in buckets).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    buckets, unpack = fuse(leaves, threshold_bytes, pad_elems=pad_elems)
    order = range(len(buckets) - 1, -1, -1) if reverse \
        else range(len(buckets))
    results: List[Any] = [None] * len(buckets)
    prev = None
    for b in order:
        buf = buckets[b]
        if pin_order and prev is not None:
            buf, prev = lax.optimization_barrier((buf, prev))
        r = fn(buf)
        results[b] = r
        prev = r
    new_leaves = unpack(results)
    return jax.tree_util.tree_unflatten(treedef, new_leaves)
