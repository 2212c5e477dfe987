"""Shared multi-head attention dispatch for the model zoo.

One definition of the dense-vs-flash choice (scale, masking constant, pallas
kernel call) used by GPT-2, BERT and ViT, so the implementations cannot
diverge. Mirrors how the reference funnels every frontend through one
attention codepath (upstream frameworks' fused kernels); here the fused path
is the pallas flash kernel.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["multihead_attention", "ATTENTION_IMPLS", "validate_sp_config",
           "sp_global_positions", "sp_attention", "packed_positions",
           "segment_mask", "block_diffusion_mask", "window_mask"]

ATTENTION_IMPLS = ("dense", "flash")

_NEG_INF = -1e30


def multihead_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, impl: str, causal: bool,
                        key_mask: Optional[jnp.ndarray] = None,
                        segment_ids: Optional[jnp.ndarray] = None,
                        out_dtype: Optional[jnp.dtype] = None,
                        flash_blocks: Optional[tuple] = None,
                        bias: Optional[jnp.ndarray] = None,
                        scale: Optional[float] = None,
                        block_diffusion: Optional[tuple] = None,
                        window: Optional[int] = None
                        ) -> jnp.ndarray:
    """softmax(q k^T * scale [+ bias + masks]) v over (B, T, H, D).

    Args:
      impl: "dense" (materialised scores, fp32 softmax) or "flash" (fused
        pallas kernel). Anything else raises — a typo must not silently
        train on the wrong path.
      causal: autoregressive mask.
      key_mask: optional (B, T_kv) bool; False keys are masked out
        (key-padding).
      segment_ids: optional (B, T) int — sequence-packing segment ids;
        attention is blocked across segment boundaries (q attends only
        to keys with the SAME id). Both impls: the flash kernels mask
        score tiles to same-segment pairs.
      out_dtype: dtype of the returned tensor (defaults to q.dtype).
      flash_blocks: optional (block_q, block_k) tiling override for the
        flash kernel — feed ``autotune_flash_blocks``'s pick for this
        shape; None keeps the kernel defaults. Ignored by "dense".
      bias: optional additive score bias, (H, T_q, T_kv) or
        (B, H, T_q, T_kv) fp32 — T5-style per-head relative position
        biases. DENSE ONLY: the flash kernel's fused bias is per-key
        (``key_bias``) and cannot express a 2-D per-head tensor, so
        passing one with impl="flash" raises.
      scale: logit scale override; default ``1/sqrt(head_dim)`` (T5
        famously uses 1.0 — folded into its initializer).
      block_diffusion: optional static ``(seq_len, block_len)``: the rows
        are ``[noisy ; clean]``, ``2 * seq_len`` positions, masked by
        :func:`block_diffusion_mask`. Both impls; the flash kernels skip
        the tiles that hold no visible pair.
      window: optional static number of keys a query sees, its own
        included (sliding-window attention, :func:`window_mask`; needs
        ``causal``). Both impls; the flash kernels skip what lies wholly
        under the band.

    Returns (B, T_q, H, D).
    """
    if impl not in ATTENTION_IMPLS:
        raise ValueError(
            f"unknown attention impl {impl!r}; expected one of "
            f"{ATTENTION_IMPLS}")
    out_dtype = q.dtype if out_dtype is None else out_dtype
    d = q.shape[-1]

    if impl == "flash":
        if bias is not None:
            raise ValueError(
                "per-head 2-D attention bias requires impl='dense' (the "
                "flash kernel's fused bias is per-key only)")
        from horovod_tpu.ops.flash_attention import flash_attention
        key_bias = None
        if key_mask is not None:
            key_bias = jnp.where(key_mask, 0.0, _NEG_INF).astype(jnp.float32)
        blocks = {}
        if flash_blocks is not None:
            blocks = {"block_q": int(flash_blocks[0]),
                      "block_k": int(flash_blocks[1])}
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               key_bias=key_bias,
                               segment_ids=segment_ids,
                               block_diffusion=block_diffusion,
                               window=window, **blocks).astype(out_dtype)

    scale = d ** -0.5 if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        b = bias if bias.ndim == 4 else bias[None]
        s = s + b.astype(jnp.float32)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :], s, _NEG_INF)
    if segment_ids is not None:
        s = jnp.where(segment_mask(segment_ids, segment_ids)[:, None],
                      s, _NEG_INF)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if window is not None:
        if not causal or block_diffusion is not None:
            raise ValueError(f"window={window} needs causal=True and no "
                             "block_diffusion")
        mask = window_mask(jnp.arange(q.shape[1])[:, None],
                           jnp.arange(k.shape[1])[None, :], window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if block_diffusion is not None:
        seq_len, block_len = block_diffusion
        if q.shape[1] != 2 * seq_len or k.shape[1] != 2 * seq_len:
            raise ValueError(
                f"block_diffusion=({seq_len}, {block_len}) needs "
                f"{2 * seq_len} positions, got {q.shape[1]} x {k.shape[1]}")
        pos = jnp.arange(2 * seq_len, dtype=jnp.int32)
        mask = block_diffusion_mask(pos[:, None], pos[None, :], seq_len,
                                    block_len)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(out_dtype)
    if key_mask is not None or segment_ids is not None:
        # A row whose keys are all masked softmaxes to uniform garbage;
        # return zeros instead, matching the flash kernel's contract.
        # Visibility comes from the COMBINED scores (key mask AND segment
        # mask can each empty a row that the other leaves populated).
        any_visible = (s.max(axis=-1) > _NEG_INF / 2)[..., None]
        p = jnp.where(any_visible, p, 0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def validate_sp_config(cfg) -> None:
    """Shared config guards for the sequence-parallel attention dispatch.

    Reads ``use_ring_attention / attention / sp_impl / ring_layout`` off any
    model config (GPT-2, Llama). Raises on typos rather than silently
    training on the wrong path — a bad ``ring_layout`` in particular would
    index contiguous positions against striped-ordered tokens: wrong
    logits, no error.
    """
    if not cfg.use_ring_attention:
        return
    if cfg.attention not in ("dense", "flash"):
        raise ValueError(
            f"unknown attention impl {cfg.attention!r} for the ring "
            "path; expected 'dense' or 'flash'")
    if cfg.sp_impl not in ("ring", "ulysses"):
        raise ValueError(
            f"unknown sp_impl {cfg.sp_impl!r}; expected 'ring' or "
            "'ulysses'")
    if cfg.ring_layout not in ("contiguous", "striped"):
        raise ValueError(
            f"unknown ring_layout {cfg.ring_layout!r}; expected "
            "'contiguous' or 'striped'")
    if cfg.sp_impl == "ulysses" and cfg.ring_layout == "striped":
        raise ValueError(
            "ulysses sequence parallelism gathers the full sequence "
            "per head — positions are globally contiguous; use "
            "ring_layout='contiguous' (striped positions would mask the "
            "wrong pairs: wrong logits, no error)")


def sp_global_positions(T: int, cfg, axis_name: str = "sp") -> jnp.ndarray:
    """Global token positions for this sequence-parallel shard: (T,) int.

    Positional state (GPT-2's wpe rows, Llama's RoPE angles) must follow
    the shard's *global* positions — rank-major for the contiguous layout,
    rank-offset stride-n for the striped one. Without sequence parallelism
    this is just ``arange(T)``.
    """
    pos = jnp.arange(T)
    if not cfg.use_ring_attention:
        return pos
    if cfg.ring_layout == "striped":
        n = jax.lax.psum(1, axis_name)
        return jax.lax.axis_index(axis_name) + n * pos
    return pos + jax.lax.axis_index(axis_name) * T


def segment_mask(seg_q: jnp.ndarray, seg_k: jnp.ndarray) -> jnp.ndarray:
    """(B, Tq, Tk) bool — True where q and k belong to the same packing
    segment. THE definition of cross-document blocking; every dense path
    (local, ring step, ulysses) masks through this one helper."""
    return seg_q[:, :, None] == seg_k[:, None, :]


def block_diffusion_mask(q_pos, k_pos, seq_len: int, block_len: int):
    """Visibility under block-diffusion training, where a row is
    ``[noisy ; clean]``: positions ``0 .. seq_len-1`` are the noised copy,
    ``seq_len .. 2*seq_len-1`` the clean one, and both count their blocks of
    ``block_len`` from their own start. A noisy query sees the noisy keys of
    its own block and the clean keys of the blocks before it; a clean query
    sees the clean keys of its own block and of those before it, and no
    noisy key. THE definition: the dense path and the three flash kernels
    mask through it. ``q_pos`` ``(Tq, 1)`` and ``k_pos`` ``(1, Tk)`` int32
    (or any shapes that broadcast); returns their broadcast, bool."""
    if block_len & (block_len - 1):
        block_of = lambda x: jax.lax.div(x, jnp.int32(block_len))
    else:       # a shift: no vector division inside a kernel
        shift = block_len.bit_length() - 1
        block_of = lambda x: jax.lax.shift_right_logical(x, jnp.int32(shift))
    q_noisy, k_noisy = q_pos < seq_len, k_pos < seq_len
    q_blk = block_of(jnp.where(q_noisy, q_pos, q_pos - seq_len))
    k_blk = block_of(jnp.where(k_noisy, k_pos, k_pos - seq_len))
    # Two comparisons of a column with a row, in integers (a select between
    # booleans does not lower in a kernel). A noisy key is seen by the noisy
    # queries of its own block: a clean query's block reads -1 and a clean
    # key's -2 here, which match nothing. A clean key is seen from the
    # blocks after it, and from its own by a clean query: a noisy key's
    # block reads as past every block.
    same = jnp.where(q_noisy, q_blk, -1) == jnp.where(k_noisy, k_blk, -2)
    before = (jnp.where(k_noisy, jnp.int32(2 ** 30), k_blk)
              < q_blk + jnp.where(q_noisy, 0, 1))
    return same | before


def window_mask(q_pos, k_pos, window: int):
    """The lower edge of a sliding window over a causal mask: a query sees
    the ``window`` keys up to and including its own, so a key is hidden once
    it lies ``window`` or more positions back. THE definition for the dense
    path; the flash kernels mask by the same inequality on their tiles
    (``ops/flash_attention._mask_scores``). ``q_pos`` ``(Tq, 1)`` and
    ``k_pos`` ``(1, Tk)`` (or any shapes that broadcast); returns their
    broadcast, bool. The causal mask is the other edge and is not in it."""
    return q_pos - k_pos < window


def packed_positions(segment_ids: jnp.ndarray) -> jnp.ndarray:
    """(B, T) positions that restart at 0 at every segment boundary.

    Sequence packing gives each packed document its own positional
    indices (wpe rows / RoPE angles); segments must be contiguous runs
    (the packed layout). Feed the result to a model's ``positions``
    input alongside ``segment_ids``.
    """
    T = segment_ids.shape[1]
    ar = jnp.broadcast_to(jnp.arange(T)[None, :], segment_ids.shape)
    prev = jnp.concatenate(
        [segment_ids[:, :1] - 1, segment_ids[:, :-1]], axis=1)
    starts = jax.lax.cummax(jnp.where(segment_ids != prev, ar, 0), axis=1)
    return ar - starts


def sp_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, cfg,
                 axis_name: str = "sp", causal: bool = True,
                 key_mask=None, segment_ids=None) -> jnp.ndarray:
    """One dispatch for the zoo's self-attention paths (causal decoders
    and, with ``causal=False``, bidirectional encoders).

    ``cfg`` carries the selection (``use_ring_attention / sp_impl /
    attention / ring_layout / flash_blocks / dtype``):

    * no sp            -> ``multihead_attention`` (dense or pallas flash)
    * sp_impl="ring"   -> ring attention over ``axis_name`` (dense or
                          flash backward-ring, contiguous/striped layouts)
    * sp_impl="ulysses"-> all-to-all heads<->sequence, then local attention

    ``key_mask`` is this shard's (B, t_local) bool key-padding mask and
    ``segment_ids`` its (B, t_local) int sequence-packing ids — both
    supported on EVERY path: the rings rotate the k-side copies with
    their K/V block, ulysses allgathers them, and the flash kernels mask
    score tiles natively.

    Used by GPT-2, Llama and BERT so the dispatch cannot diverge between
    model families (the configs validate via :func:`validate_sp_config`).
    """
    if cfg.use_ring_attention:
        if cfg.sp_impl == "ulysses":
            from horovod_tpu.ops.sequence import ulysses_attention
            blocks = {}
            if cfg.flash_blocks is not None:
                blocks = {"block_q": int(cfg.flash_blocks[0]),
                          "block_k": int(cfg.flash_blocks[1])}
            return ulysses_attention(q, k, v, axis_name=axis_name,
                                     causal=causal, impl=cfg.attention,
                                     key_mask=key_mask,
                                     segment_ids=segment_ids, **blocks)
        if cfg.attention == "flash":
            from horovod_tpu.ops.ring_flash import ring_flash_attention
            return ring_flash_attention(q, k, v, axis_name=axis_name,
                                        causal=causal,
                                        layout=cfg.ring_layout,
                                        key_mask=key_mask,
                                        segment_ids=segment_ids)
        if cfg.attention == "dense":
            from horovod_tpu.ops.ring_attention import ring_attention
            return ring_attention(q, k, v, axis_name=axis_name,
                                  causal=causal, layout=cfg.ring_layout,
                                  key_mask=key_mask,
                                  segment_ids=segment_ids)
        raise ValueError(
            f"unknown attention impl {cfg.attention!r} for the ring "
            "path; expected 'dense' or 'flash'")
    return multihead_attention(q, k, v, impl=cfg.attention, causal=causal,
                               key_mask=key_mask, segment_ids=segment_ids,
                               out_dtype=cfg.dtype,
                               flash_blocks=cfg.flash_blocks)
