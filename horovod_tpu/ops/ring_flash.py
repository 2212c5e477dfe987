"""Ring flash attention: exact attention over device-sharded sequences with
the pallas flash kernel as the per-block compute.

`ring_attention.py` holds the jnp-level reference implementation (scores
materialised per block, autodiff backward). This module is the production
path for long context: each ring step runs the fused flash kernel
(VMEM-tiled, MXU matmuls) on the resident K/V block, and the backward pass
is a hand-written second ring that reuses the flash backward kernels —
dK/dV partial sums travel around the ring with their blocks, so gradients
for every block arrive back at its home device after n hops. (Liu et al.
2023 blockwise ring attention; FlashAttention-2 block math. PAPERS.md
lineage.)

Causality across shards decomposes per (query-shard r, key-shard src) into
three static kernel modes — full (src < r), local-causal (src == r), and
skip (src > r) — selected at runtime with ``lax.switch``; global softmax
normalisation uses the per-block logsumexp merged in log space.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Import from the module path directly: the package __init__ rebinds the
# name `flash_attention` to the public function, shadowing the module.
from horovod_tpu.ops.flash_attention import _bwd as _fa_bwd
from horovod_tpu.ops.flash_attention import _fwd as _fa_fwd

__all__ = ["ring_flash_attention"]

_NEG_INF = -1e30


def _pack(x):
    # (B, T, H, D) -> (B*H, T, D)
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unpack(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _block_fwd(q, k, v, bias, seg_q, seg_k, h, causal, scale, bq, bk,
               offset=0):
    """One flash forward on packed arrays → (o f32 (bh,t,d), lse (bh,t)).
    ``bias`` is the resident K block's (b, tk, 1) additive logit bias
    (key-padding) — the kernel broadcasts it over the h heads folded into
    the packed batch rows — or None. ``seg_q``/``seg_k`` are the home
    q-side and resident k-side (b, t, 1) segment ids, or None."""
    o, lse = _fa_fwd(q, k, v, bias, seg_q, seg_k, h, scale, causal, bq, bk,
                     offset=offset)
    return o.astype(jnp.float32), lse[..., 0]


def _safe_merge(o_acc, lse_acc, o_b, lse_b):
    """Log-space merge of two normalised partial attentions."""
    lse_new = jnp.logaddexp(lse_acc, lse_b)
    # exp(-1e30 - -1e30) would be 1; gate on the accumulator being live.
    w_acc = jnp.where(lse_acc > _NEG_INF / 2,
                      jnp.exp(lse_acc - lse_new), 0.0)
    w_b = jnp.where(lse_b > _NEG_INF / 2, jnp.exp(lse_b - lse_new), 0.0)
    o_new = o_acc * w_acc[..., None] + o_b * w_b[..., None]
    return o_new, lse_new


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _ring(q, k, v, bias, seg, axis_name, causal, scale, bq, bk, striped, h,
          want_dbias):
    o, _ = _ring_fwd_impl(q, k, v, bias, seg, axis_name, causal, scale,
                          bq, bk, striped, h)
    return o


def _mode_of(striped, causal, src, rank):
    """Per-step kernel mode. Contiguous: full / local-causal / skip.
    Striped (Striped Attention): every pair carries ~half the causal
    triangle — causal for src <= rank, strict-causal (diagonal excluded,
    causal_offset=-1) for src > rank — so no step is ever fully masked or
    fully idle: the ring's causal work is balanced across devices."""
    if not causal:
        return jnp.int32(0)
    if striped:
        return jnp.where(src <= rank, 1, 3)
    return jnp.where(src < rank, 0, jnp.where(src == rank, 1, 2))


def _ring_fwd_impl(q, k, v, bias, seg, axis_name, causal, scale, bq, bk,
                   striped, h=1):
    n = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    bh, tq, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def full_b(q, k, v, bias, seg_k):
        return _block_fwd(q, k, v, bias, seg, seg_k, h, False, scale, bq,
                          bk)

    def causal_b(q, k, v, bias, seg_k):
        return _block_fwd(q, k, v, bias, seg, seg_k, h, True, scale, bq,
                          bk)

    def skip_b(q, k, v, bias, seg_k):
        return (jnp.zeros((bh, tq, d), jnp.float32),
                jnp.full((bh, tq), _NEG_INF, jnp.float32))

    def strict_b(q, k, v, bias, seg_k):
        return _block_fwd(q, k, v, bias, seg, seg_k, h, True, scale, bq,
                          bk, offset=-1)

    def step(carry, i):
        o_acc, lse_acc, k, v, bias, seg_k = carry
        if not causal:
            # Every hop is a full block: no mode switch, and no
            # axis_index feeding a dead branch selector (whose constant-
            # folded remnant old XLA SPMD pipelines reject as a bare
            # PartitionId).
            o_b, lse_b = full_b(q, k, v, bias, seg_k)
        else:
            src = (rank - i) % n
            mode = _mode_of(striped, causal, src, rank)
            o_b, lse_b = lax.switch(mode,
                                    [full_b, causal_b, skip_b, strict_b],
                                    q, k, v, bias, seg_k)
        o_acc, lse_acc = _safe_merge(o_acc, lse_acc, o_b, lse_b)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        if bias is not None:
            # the key-padding bias travels with its K block
            bias = lax.ppermute(bias, axis_name, perm)
        if seg_k is not None:
            # the k-side segment ids travel with their K block too
            seg_k = lax.ppermute(seg_k, axis_name, perm)
        return (o_acc, lse_acc, k, v, bias, seg_k), None

    o0 = jnp.zeros((bh, tq, d), jnp.float32)
    lse0 = jnp.full((bh, tq), _NEG_INF, jnp.float32)
    (o, lse, k, v, bias, _), _ = lax.scan(step, (o0, lse0, k, v, bias,
                                                 seg), jnp.arange(n))
    return o.astype(q.dtype), lse


def _ring_fwd(q, k, v, bias, seg, axis_name, causal, scale, bq, bk,
              striped, h, want_dbias):
    o, lse = _ring_fwd_impl(q, k, v, bias, seg, axis_name, causal, scale,
                            bq, bk, striped, h)
    return o, (q, k, v, bias, seg, o, lse)


def _ring_bwd(axis_name, causal, scale, bq, bk, striped, h, want_dbias,
              res, do):
    q, k, v, bias, seg, o, lse = res
    n = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    do = do.astype(q.dtype)
    lse_in = lse[..., None]
    # delta = dO.O is invariant across ring hops; compute once, not per step.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)

    track_db = bias is not None and want_dbias

    def grads_block(q, k, v, bias, seg_k, causal_mode, offset=0):
        # Reuse the flash backward kernels with the *global* lse and the
        # precomputed global delta: p then equals the globally-normalised
        # attention prob of this block.
        dq, dk, dv, db = _fa_bwd(
            h, scale, causal_mode, bq, bk,
            (q, k, v, bias, seg, seg_k, o, lse_in),
            do, delta=delta, offset=offset, want_db=track_db)
        return (dq.astype(jnp.float32), dk.astype(jnp.float32),
                dv.astype(jnp.float32),
                None if db is None else db.astype(jnp.float32))

    def full_b(q, k, v, bias, seg_k):
        return grads_block(q, k, v, bias, seg_k, False)

    def causal_b(q, k, v, bias, seg_k):
        return grads_block(q, k, v, bias, seg_k, True)

    def skip_b(q, k, v, bias, seg_k):
        return (jnp.zeros(q.shape, jnp.float32),
                jnp.zeros(k.shape, jnp.float32),
                jnp.zeros(v.shape, jnp.float32),
                None if not track_db else jnp.zeros(bias.shape,
                                                    jnp.float32))

    def strict_b(q, k, v, bias, seg_k):
        return grads_block(q, k, v, bias, seg_k, True, offset=-1)

    def step(carry, i):
        dq_acc, k, v, bias, seg_k, dk_acc, dv_acc, db_acc = carry
        if not causal:
            # Mirror of the forward's non-causal fast path (see
            # _ring_fwd_impl.step).
            dq_b, dk_b, dv_b, db_b = full_b(q, k, v, bias, seg_k)
        else:
            src = (rank - i) % n
            mode = _mode_of(striped, causal, src, rank)
            dq_b, dk_b, dv_b, db_b = lax.switch(
                mode, [full_b, causal_b, skip_b, strict_b], q, k, v, bias,
                seg_k)
        dq_acc = dq_acc + dq_b
        dk_acc = dk_acc + dk_b
        dv_acc = dv_acc + dv_b
        # dK/dV (and dBias) partial sums travel with their K/V block;
        # after n hops the block (and its completed gradient) is home
        # again.
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
        if bias is not None:
            bias = lax.ppermute(bias, axis_name, perm)
        if seg_k is not None:
            seg_k = lax.ppermute(seg_k, axis_name, perm)
        if track_db:
            # the bias cotangent ships home with its block, like dK/dV
            db_acc = db_acc + db_b
            db_acc = lax.ppermute(db_acc, axis_name, perm)
        return (dq_acc, k, v, bias, seg_k, dk_acc, dv_acc, db_acc), None

    z = jnp.zeros(q.shape, jnp.float32)
    zk = jnp.zeros(k.shape, jnp.float32)
    db0 = None if not track_db else jnp.zeros(bias.shape, jnp.float32)
    (dq, k, v, bias, _, dk, dv, db), _ = lax.scan(
        step, (z, k, v, bias, seg, zk, jnp.zeros_like(zk), db0),
        jnp.arange(n))
    # A mask-derived bias (want_dbias=False) gets a zero cotangent — it
    # dies into jnp.where constants anyway; skipping the accumulate +
    # per-hop ppermute keeps the hot masked-sp path free of dead traffic.
    if bias is not None and db is None:
        db = jnp.zeros(bias.shape, jnp.float32)
    dseg = (None if seg is None
            else np.zeros(seg.shape, dtype=jax.dtypes.float0))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            db, dseg)


_ring.defvjp(_ring_fwd, _ring_bwd)


def ring_flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         axis_name: str, causal: bool = True,
                         scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         layout: str = "contiguous",
                         key_mask: Optional[jnp.ndarray] = None,
                         segment_ids: Optional[jnp.ndarray] = None,
                         window: Optional[int] = None) -> jnp.ndarray:
    """Exact attention with q/k/v sequence-sharded across ``axis_name``.

    Same contract as ``ring_attention`` (including the ``layout`` arg),
    but the per-block compute is the fused pallas flash kernel and the
    backward pass is a second explicit ring. Use inside
    ``shard_map``/``hvd.spmd``.

    With ``causal`` + the contiguous layout the ring is load-imbalanced:
    device r skips n-r-1 of its n steps (fully masked blocks), but the
    ppermute barrier makes everyone wait for the busiest device — wall
    clock ≈ the unmasked cost. ``layout="striped"`` (Striped Attention,
    Brandon et al. 2023) interleaves positions so EVERY (q, kv) pair
    carries ~half the triangle: each step costs ~half a full block on every
    device simultaneously, recovering the ~2x causal saving at scale.

    Args:
      q, k, v: (batch, t_local, heads, head_dim) — this device's shard.
      axis_name: mesh axis the sequence is sharded over.
      causal: global causal mask.
      scale: logit scale; defaults to head_dim**-0.5.
      block_q, block_k: flash kernel tile sizes; ``None`` (default)
        consults the checked-in tile table (``ops/tile_table.py``,
        kind="ring": the per-hop sequence is the local shard and the
        backward is a second explicit ring, so the VMEM profile differs
        from single-device flash).
      key_mask: optional (batch, t_local) bool — this shard's key-padding
        mask (False keys masked out). It becomes the kernel's additive
        key bias and travels around the ring with its K/V block (the
        backward ships the bias cotangent home the same way, so a
        future differentiable bias rides for free).
      segment_ids: optional (batch, t_local) int — this shard's
        sequence-packing segment ids. The k-side copy travels around the
        ring with its K/V block; each hop's kernel masks score tiles to
        same-segment (home-q, resident-k) pairs.
      window: not built. A sliding window over a ring needs each hop's
        kernel told how far its keys lie behind its queries and the hops
        wholly under the band left out; a value raises rather than attend
        over the whole causal triangle.

    Returns (batch, t_local, heads, head_dim), dtype of ``q``.
    """
    if window is not None:
        raise ValueError(
            f"window={window}: ring flash attention has no sliding window "
            "(flash_attention on one device has)")
    b, t, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    if block_q is None or block_k is None:
        from horovod_tpu.ops import tile_table
        tq_, tk_ = tile_table.lookup(d, t, q.dtype, "ring")
        block_q = tq_ if block_q is None else block_q
        block_k = tk_ if block_k is None else block_k
    if layout not in ("contiguous", "striped"):
        raise ValueError(f"unknown layout {layout!r}; expected "
                         "'contiguous' or 'striped'")
    bias = None
    if key_mask is not None:
        if key_mask.shape != (b, t):
            raise ValueError(
                f"key_mask must be (batch, t_local) = ({b}, {t}), got "
                f"{key_mask.shape}")
        # (b, tk, 1): the kernel's bias spec broadcasts over the h heads
        # folded into the packed batch rows, so the ring only ever ships
        # the per-batch bias, not h copies.
        bias = jnp.where(key_mask, 0.0, _NEG_INF
                         ).astype(jnp.float32)[..., None]
    seg = None
    if segment_ids is not None:
        if segment_ids.shape != (b, t):
            raise ValueError(
                f"segment_ids must be (batch, t_local) = ({b}, {t}), got "
                f"{segment_ids.shape}")
        seg = segment_ids.astype(jnp.int32)[..., None]
    o = _ring(_pack(q), _pack(k), _pack(v), bias, seg, axis_name,
              bool(causal), float(scale), int(block_q), int(block_k),
              layout == "striped", h, False)
    return _unpack(o, b, h)
