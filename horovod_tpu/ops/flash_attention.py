"""Fused flash attention as a Pallas TPU kernel.

The hot op of every transformer in `horovod_tpu/models`. The reference stack
reaches fused attention through vendor libraries on GPU (upstream Horovod
defers to framework kernels, e.g. torch SDPA); on TPU we own the kernel:
a Pallas implementation of the FlashAttention-2 scheme (Dao 2023, PAPERS.md
lineage) tiled for the MXU.

Design (tpu-first):
- Grid ``(batch*heads, num_q_blocks, num_k_blocks)`` — the K dimension is the
  innermost (sequential) grid axis, so fp32 accumulators for the online
  softmax live in VMEM scratch and persist across K steps. One HBM pass over
  K/V per Q block; O(block_q * block_k) VMEM for scores instead of O(T^2).
- QK^T and PV ride the MXU via ``jnp.dot(..., preferred_element_type=f32)``;
  the online-softmax rescale is VPU work fused in between.
- Causal masking skips whole K blocks past the diagonal with ``@pl.when``
  (no FLOPs burned above the diagonal beyond one partial block per row) —
  where the grid has K blocks to skip. A shape whose tile-table entry
  carries a compute ``chunk`` (head 64 / T 1024, swept forward and backward
  on the v5e) is tiled at two levels instead: the K tile is the whole key
  axis, resident for the head, the grid's K axis has one step, and the
  kernels loop inside the step over chunks of the keys, as many as
  the mask leaves visible to this Q tile (``_chunk_runs``,
  ``_chunk_loop``): up to the diagonal under the causal mask, from the
  band's lower edge under a window, a noisy Q tile's own chunk and the
  clean prefix under the block-diffusion mask (``_bd_chunks``). Chunks no
  edge of the mask crosses take no positional mask; only those one
  crosses go through ``_mask_scores``. The forward
  carries its softmax state round the loop as values (a chunk's
  read-modify-write of the 1-D scratch costs more than the chunk's
  scores), the backward keeps its sums in scratch. An entry
  without a chunk runs the kernels as they always were. A resident K tile
  takes VMEM (K and V twice, the backward's fp32 dK / dV sums, dK and dV
  twice on their way out: 43 MB at head 64 and 73 MB at head 256 for 8,192
  keys): such a call asks the compiler for what ``_vmem_need`` counts
  where the 16 MiB default does not cover it, and where a core could not
  give it ``_tiling`` hands back the plain grid.
- Sequence lengths need not divide the block size: the grid is ``cdiv`` and
  the ragged edge blocks are position-masked (ViT's 197 tokens, odd context
  lengths). Tiling — and the VMEM bound — is preserved.
- ``key_bias`` adds a per-(batch, key) additive logit bias, the TPU shape of
  the reference's attention masks (BERT key-padding = 0/-inf bias).
- Backward is the standard flash recomputation, wired up with
  ``jax.custom_vjp``; residuals are O and the per-row logsumexp only. On a
  plain grid it is two kernels, dQ (grid over Q blocks) and dK/dV (grid
  over K blocks), each of which recomputes the scores of every pair it
  visits. Where the K tile is resident with a loop of chunks it is one:
  the dK/dV kernel's grid walks the Q tiles, a Q tile meets all of its
  keys inside one grid step, and its dQ is summed there from the same
  ``ds`` (``flash_dkv`` alone; five products and one pass of the
  exponential a pair instead of seven and two): one shape of the backward
  under the causal, the window and the block-diffusion mask. A tracked
  key-bias gradient, the ring's calls and a call that names its own tiles
  keep two.
- Off-TPU (the virtual CPU test mesh) the same kernels run in Pallas
  interpreter mode, so tests exercise the real kernel code path.

Tiles come from the checked-in tile table (``ops/tile_table.py``,
``flash_tiles.json``), by (head_dim, seq, dtype, kind of mask), measured on
the chip by ``tools/tune_tiles.py``; a shape with no entry near it takes the
table's default (256, 512). They are clamped to the sequence length for
small inputs. What a visit of a tile costs on the v5e is mostly fixed (the
chain matmul, softmax, matmul of one pair does not overlap the next pair's),
so larger tiles and chunks win until the scores they waste above the
diagonal outweigh it: 128-wide ones lose everywhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import tracing as _tracing
from horovod_tpu.ops.attention import block_diffusion_mask

__all__ = ["flash_attention"]

_NEG_INF = -1e30
# VMEM, in bytes. What Mosaic gives a kernel that asks for nothing (its
# default scoped limit) and what a call may ask of a v5e core's 128 MiB (the
# rest is the compiler's own). The compiled kernels hold about 2.5 fp32
# temporaries the size of one pair's scores at once (the v5e's compiler,
# asked at which limit each shape starts to compile: _vmem_need is within
# a MiB of it at head 256 and above it at head 64), and a call asks for a
# quarter more than it counts.
_VMEM_DEFAULT = 16 * 2 ** 20
_VMEM_CAP = 100 * 2 ** 20
_VMEM_PAIR_TEMPS = 2.5
_VMEM_MARGIN = 1.25
# What the forward rule names of its own outputs (``checkpoint_name``): the
# attention output and the row log-sum-exp, both O(T) and all the backward
# kernels need of the forward besides its inputs.
RESIDUAL_NAMES = ("flash_out", "flash_lse")
# Kernel names. A Mosaic custom call is named after the last scope round
# it, which ``pallas_call(name=)`` sets: flash_fwd, flash_dq, flash_dkv
# (tracing.NAMES; the per-kernel metrics sum device time by them). jax
# wraps the outermost scope inside a transformation in the transformation's
# name (``jvp(flash_fwd)`` would name the call ``jvp_flash_fwd_``), so each
# call sits in a ``flash_attention`` scope that takes the wrapping instead.


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block_sizes(tq: int, tk: int, block_q: int, block_k: int):
    return min(block_q, tq), min(block_k, tk)


def _mask_scores(s, q_blk, kv_blk, *, block_q, block_k, tq, tk, causal,
                 offset=0, bias=None, seg_q=None, seg_k=None, bd=None,
                 window=None):
    """Apply causal / window / block-diffusion / ragged-edge / key-bias
    masking to a score block.

    Shared by the forward and both backward kernels so the mask definition
    cannot diverge between passes. ``s`` is (block_q, block_k) fp32.
    ``offset`` shifts the causal diagonal: visible iff
    ``q_pos + offset >= k_pos`` (offset -1 = strict causal — what striped
    ring layouts need for the src > rank blocks); with a ``window`` also
    ``q_pos + offset - k_pos < window``, the causal mask's lower edge.
    ``bd`` is the static ``(seq_len, block_len)`` of a block-diffusion row
    ``[noisy ; clean]`` (``ops.attention.block_diffusion_mask``).
    """
    need_pos = causal or tq % block_q or tk % block_k
    if bias is not None:
        s = s + bias
    if seg_q is not None:
        # sequence packing: visible iff q and k share a segment id
        s = jnp.where(seg_q == seg_k.reshape(1, -1), s, _NEG_INF)
    if need_pos:
        q_pos = (q_blk * block_q +
                 jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        k_pos = (kv_blk * block_k +
                 jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        ok = jnp.logical_and(q_pos < tq, k_pos < tk)
        if causal:
            ok = jnp.logical_and(ok, q_pos + offset >= k_pos)
            if window is not None:
                ok = jnp.logical_and(ok, q_pos + offset - k_pos < window)
        s = jnp.where(ok, s, _NEG_INF)
    if bd is not None:
        # positions past the ragged edge were masked above; here a column of
        # query positions against a row of key positions
        q_pos = (q_blk * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (s.shape[0], 1), 0))
        k_pos = (kv_blk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, s.shape[1]), 1))
        s = jnp.where(block_diffusion_mask(q_pos, k_pos, *bd), s, _NEG_INF)
    return s


def _zero_oob_rows(x, blk, block: int, t: int):
    """Zero rows of a (block, d) tile that fall past the sequence end.

    Ragged edge blocks read out-of-bounds memory (NaN in interpret mode,
    garbage on hardware); zeroing the rows keeps them out of the matmuls —
    0 * NaN would otherwise poison valid entries.
    """
    if t % block == 0:
        return x
    rows = blk * block + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < t, x, 0.0)


def _causal_skip(causal: bool, q_blk, kv_idx, block_q: int, block_k: int,
                 offset: int = 0, xp=jnp):
    """True when this (q, kv) block pair has any visible entries. Scalars
    in a kernel; arrays of tile indices and ``xp=np`` in
    :func:`causal_tiles`."""
    return xp.logical_or(
        xp.logical_not(causal),
        kv_idx * block_k < (q_blk + 1) * block_q + offset)


def _bd_skip(q_blk, kv_idx, block_q: int, block_k: int, seq: int,
             blk: int, xp=jnp):
    """True when this (q, kv) block pair of a block-diffusion row
    ``[noisy ; clean]`` holds a visible pair: the noisy parts of both share
    a block, or a clean key lies in a block before a noisy query's, or not
    after a clean query's. Scalars in a kernel; arrays of tile indices and
    ``xp=np`` in :func:`bd_tiles`."""
    q0, k0 = q_blk * block_q, kv_idx * block_k
    q1 = xp.minimum(q0 + block_q, 2 * seq) - 1        # last position held
    k1 = xp.minimum(k0 + block_k, 2 * seq) - 1
    q_noisy, k_noisy = q0 < seq, k0 < seq               # holds a noisy part
    q_clean, k_clean = q1 >= seq, k1 >= seq             # holds a clean part
    qn_lo, qn_hi = q0 // blk, xp.minimum(q1, seq - 1) // blk
    kn_lo, kn_hi = k0 // blk, xp.minimum(k1, seq - 1) // blk
    qc_hi = (xp.maximum(q1, seq) - seq) // blk
    kc_lo = (xp.maximum(k0, seq) - seq) // blk
    return ((q_noisy & k_noisy & (qn_lo <= kn_hi) & (kn_lo <= qn_hi))
            | (q_noisy & k_clean & (kc_lo < qn_hi))
            | (q_clean & k_clean & (kc_lo <= qc_hi)))


def _window_skip(q_blk, kv_idx, block_q: int, block_k: int, offset: int,
                 window: int):
    """True when the last key of K tile ``kv_idx`` lies inside the window of
    the first row of Q tile ``q_blk``: the tile is not wholly under the
    band. Scalars in a kernel; arrays of tile indices in
    :func:`window_tiles`."""
    return (kv_idx + 1) * block_k + window > q_blk * block_q + offset + 1


def _tile_visible(causal: bool, bd, q_blk, kv_idx, block_q: int,
                  block_k: int, offset: int = 0, window=None, xp=jnp):
    """The tile skip of the three kernels: the block-diffusion one for a
    ``bd`` row, else the causal one, with a ``window`` between the
    diagonal and the band's lower edge."""
    if bd is not None:
        return _bd_skip(q_blk, kv_idx, block_q, block_k, *bd, xp=xp)
    visible = _causal_skip(causal, q_blk, kv_idx, block_q, block_k, offset,
                           xp)
    if window is None:
        return visible
    return xp.logical_and(visible, _window_skip(q_blk, kv_idx, block_q,
                                                block_k, offset, window))


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a ``(rows, cols)`` block as VMEM lays it out: 128 lanes
    (a head of 64 takes what one of 128 does, a ``(rows, 1)`` column 128
    times its numbers) by sublanes of 32 bits."""
    sub = 8 * 4 // itemsize
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def _vmem_need(kernel: str, bq: int, bk: int, chunk, d: int, itemsize: int,
               per_key: int = 0, per_q: int = 0, extra: str = "") -> int:
    """Bytes of VMEM a call of ``kernel`` (``fwd``, ``dq``, ``dkv``) is to
    ask for at these tiles, margin included: every block in and out twice
    (the pipeline's double buffer: with a resident K tile that is K and V
    twice, and dK and dV twice on their way out), the fp32 sums in scratch
    once, the fp32 copies of the operands of one (Q tile, K tile or chunk)
    pair, and the fp32 temporaries the size of its scores. ``per_key`` /
    ``per_q`` count the ``(T, 1)`` inputs that follow the K / Q tile (key
    bias, segment ids); ``extra`` is what the dK/dV kernel yields besides:
    ``db`` or ``dq``."""
    ck = chunk or bk
    q_tile, k_tile = _tile_bytes(bq, d, itemsize), _tile_bytes(bk, d, itemsize)
    q_col, k_col = _tile_bytes(bq, 1, 4), _tile_bytes(bk, 1, 4)
    q_sum, k_sum = _tile_bytes(bq, d, 4), _tile_bytes(bk, d, 4)
    blocks = 2 * k_tile + per_key * k_col + per_q * q_col
    pair = 2 * _tile_bytes(ck, d, 4)                    # k, v of the pair
    if kernel == "fwd":
        blocks += 2 * q_tile + q_col                    # q | o, lse
        scratch = 0 if chunk else q_sum + 2 * _tile_bytes(1, bq, 4)
        pair += 2 * q_sum                               # q, the output's sum
    elif kernel == "dq":
        blocks += 3 * q_tile + 2 * q_col                # q, do, lse, delta | dq
        scratch = q_sum
        pair += 2 * q_sum                               # q, do
    else:
        blocks += 2 * q_tile + 2 * q_col + 2 * k_tile   # ... | dk, dv
        scratch = 2 * k_sum
        pair += 2 * q_sum
        if extra == "db":
            blocks, scratch = blocks + k_col, scratch + _tile_bytes(1, bk, 4)
        elif extra == "dq":
            blocks, scratch = blocks + q_tile, scratch + q_sum
    pair += _VMEM_PAIR_TEMPS * _tile_bytes(bq, ck, 4)
    return int(_VMEM_MARGIN * (2 * blocks + scratch + pair))


def _vmem_shape(d: int, dtype, bias, seg) -> dict:
    """What :func:`_vmem_need` and :func:`_tiling` weigh a call by besides
    its tiles: head size, bytes a number, and the ``(T, 1)`` inputs that
    follow the K tile (key bias, segment ids) and the Q tile (segment
    ids)."""
    return dict(d=d, itemsize=jnp.dtype(dtype).itemsize,
                per_key=(bias is not None) + (seg is not None),
                per_q=int(seg is not None))


def _dkv_yields(chunk, track_db: bool):
    """``(chunk, extra)`` of the dK/dV kernel: with the bias's gradient
    (summed along lanes, where Mosaic takes no slice at an offset it learns
    in a loop) it takes its K tile whole and yields ``db``; without, a loop
    of chunks also yields ``dq``."""
    if track_db:
        return None, "db"
    return chunk, "dq" if chunk else ""


def _vmem_params(chunk, need: int) -> dict:
    """The ``pallas_call`` arguments of a kernel that needs ``need`` bytes
    of VMEM. A kernel with a resident K tile and a loop of chunks asks for
    them where the compiler's default does not cover them; every other call
    is compiled under the default, as it always was."""
    if chunk is None or need <= _VMEM_DEFAULT:
        return {}
    return dict(compiler_params=pltpu.CompilerParams(vmem_limit_bytes=need))


def _tiling(tq: int, tk: int, block_q: int, block_k: int, chunk,
            causal: bool, bd, d: int = 64, itemsize: int = 2,
            kernel: str = "fwd", per_key: int = 0, per_q: int = 0,
            track_db: bool = False):
    """``(block_q, block_k, chunk)`` as the kernels run them. Without a
    compute chunk (``None``) the tiles are the grid's, clamped to the
    lengths. With one (the tile table gave it, the mask is one with a
    chunk loop: the causal one, with or without a window, or a
    block-diffusion row ``bd`` whose halves are whole chunks and whole Q
    tiles; the table's K tile holds every key, and they make more than one
    chunk) the K tile is the whole key axis, in whole chunks: K and V of a
    head stay resident, the grid's K axis has one step, and a loop inside the
    step takes its place (:func:`_chunk_loop`). ``kernel`` is who asks
    (the forward, or ``dkv`` for the backward): where a core cannot give
    it the VMEM a resident K tile takes (:func:`_vmem_need` over
    ``_VMEM_CAP``: a tracked bias gradient, ``track_db``, whose kernel
    takes the K tile whole), or the keys are more than the entry's K tile
    and that tile as the grid's would not fit the compiler's default (a
    sequence longer than the entry was measured at), or a block-diffusion
    row is one the loop cannot take, the grid's K tile is the chunk."""
    bq, bk = _block_sizes(tq, tk, block_q, block_k)
    if not ((causal or bd is not None) and chunk and 0 < chunk < bk):
        return bq, bk, None
    # a block-diffusion row: whole chunks in each half, and no Q tile over
    # the seam between them (_bd_chunks)
    loops = bd is None or (bd[0] % chunk == 0 and bd[0] % bq == 0)

    def need(k_tile, chunk):
        extra = ""
        if kernel == "dkv":
            chunk, extra = _dkv_yields(chunk, track_db)
        return _vmem_need(kernel, bq, k_tile, chunk, d, itemsize, per_key,
                          per_q, extra)

    if block_k >= tk:
        whole = -(-tk // chunk) * chunk
        if loops and need(whole, int(chunk)) <= _VMEM_CAP:
            return bq, whole, int(chunk)
    elif need(bk, None) <= _VMEM_DEFAULT:       # a plain grid asks nothing
        return bq, bk, None
    return bq, int(chunk), None


def _causal_chunks(q_blk, block_q: int, chunk: int, offset: int, tk: int,
                   xp=jnp):
    """``(clear, visible)`` of the compute chunks of the key axis against Q
    tile ``q_blk`` under the causal mask: the first ``clear`` chunks lie
    wholly at or under the diagonal (every pair visible: no positional
    mask), the first ``visible`` hold a visible pair at all, the rest are
    not visited. A scalar in a kernel; an array of tile indices and
    ``xp=np`` in :func:`causal_tiles`."""
    q0 = q_blk * block_q + offset          # its first row sees keys <= q0
    visible = xp.minimum((xp.maximum(q0 + block_q, 0) + chunk - 1) // chunk,
                         -(-tk // chunk))
    clear = xp.minimum(xp.maximum(q0 + 1, 0) // chunk, visible)
    return clear, visible


def _window_chunks(q_blk, block_q: int, chunk: int, offset: int, tk: int,
                   window: int, xp=jnp):
    """``(first, inside, clear, visible)`` of the compute chunks of the key
    axis against Q tile ``q_blk`` under the causal mask with a ``window``:
    chunks ``first .. visible - 1`` hold a visible pair, and of them
    ``inside .. clear - 1`` lie wholly between the band's lower edge and the
    diagonal (no positional mask); the band's edge crosses those before
    ``inside``, the diagonal those from ``clear``. ``inside == clear`` where
    the window is too short for a chunk to lie clear of both."""
    clear, visible = _causal_chunks(q_blk, block_q, chunk, offset, tk, xp)
    q0 = q_blk * block_q + offset          # its first row sees keys > q0 - W
    first = xp.minimum(xp.maximum(q0 - window + 1, 0) // chunk, visible)
    inside = (xp.maximum(q0 + block_q - window, 0) + chunk - 1) // chunk
    inside = xp.minimum(xp.maximum(inside, first), visible)
    return first, inside, xp.maximum(clear, inside), visible


def _bd_chunks(q_blk, block_q: int, chunk: int, seq: int, blk: int, xp=jnp):
    """The runs ``(lo, hi, crossed)`` of compute chunks that Q tile ``q_blk``
    of a block-diffusion row ``[noisy ; clean]`` of ``2 * seq`` positions may
    see, for a tile that lies in one half (``seq % block_q == 0``) and
    chunks that do (``seq % chunk == 0``). A noisy tile sees the noisy
    chunks that hold its own blocks, under the mask (``crossed``: with blocks
    of 4 in chunks of hundreds nearly all of such a chunk is masked; a
    narrower diagonal is left for later), and the clean prefix: the clean
    chunks whose every key lies in a block before its first row's take no
    mask, those as far as its last row's block are masked. A clean tile
    sees no noisy chunk, and its own block too: the clean chunks whose
    every key lies in or before its first row's block take no mask, those
    as far as its last row's block are masked. Scalars in a kernel; arrays
    of tile indices and ``xp=np`` in :func:`bd_tiles`."""
    q0 = q_blk * block_q
    noisy = q0 < seq
    r0 = xp.where(noisy, q0, q0 - seq)          # from the start of its half
    own = xp.where(noisy, 0, 1)                 # a clean row sees its block
    b_lo, b_hi = r0 // blk, (r0 + block_q - 1) // blk
    base = seq // chunk                         # the first clean chunk
    clear = base + (b_lo + own) * blk // chunk
    visible = base + (xp.minimum((b_hi + own) * blk, seq) + chunk - 1) // chunk
    diag_lo = b_lo * blk // chunk
    diag_hi = (xp.minimum((b_hi + 1) * blk, seq) + chunk - 1) // chunk
    return [(xp.where(noisy, diag_lo, 0), xp.where(noisy, diag_hi, 0), True),
            (base, clear, False), (clear, visible, True)]


def _chunk_runs(q_blk, block_q: int, chunk: int, offset: int, tk: int,
                window=None, bd=None, xp=jnp):
    """The runs ``(lo, hi, crossed)`` of compute chunks of the resident K
    tile that Q tile ``q_blk`` may see, in the order the kernels take them,
    by the kind of mask. Causal: the chunks under the diagonal, which take
    no mask (``crossed=False``), then those it crosses. With a ``window``
    the chunks the band's lower edge crosses come first, a second masked
    run. A block-diffusion row ``bd``: :func:`_bd_chunks`."""
    if bd is not None:
        return _bd_chunks(q_blk, block_q, chunk, *bd, xp=xp)
    if window is None:
        clear, visible = _causal_chunks(q_blk, block_q, chunk, offset, tk, xp)
        return [(0, clear, False), (clear, visible, True)]
    first, inside, clear, visible = _window_chunks(
        q_blk, block_q, chunk, offset, tk, window, xp)
    return [(first, inside, True), (inside, clear, False),
            (clear, visible, True)]


def _chunk_loop(visit, state, q_blk, block_q: int, chunk: int, offset: int,
                tk: int, window=None, bd=None):
    """The loop inside a grid step whose length is what the mask shows:
    ``state = visit(state, ci, rows, crossed)`` over the compute chunks of
    the resident K tile that Q tile ``q_blk`` may see (:func:`_chunk_runs`),
    ``rows`` of the tile known to the mask as block ``ci`` of ``chunk``
    keys. A chunk no edge of the mask crosses (``crossed=False``) takes no
    positional mask."""
    for lo, hi, crossed in _chunk_runs(q_blk, block_q, chunk, offset, tk,
                                       window, bd):
        def body(ci, state):    # traced here, with this run's ``crossed``
            rows = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
            return visit(state, ci, rows, crossed)
        state = jax.lax.fori_loop(lo, hi, body, state)
    return state


def _tiles_visited(t: int, bq: int, bk: int, chunk, causal: bool, bd,
                   offset: int = 0, window=None):
    """``(visited, total)`` of one head's forward over ``t`` positions at
    the tiles :func:`_tiling` gave: pairs of (Q tile, compute chunk) where
    the kernels loop over chunks, else the grid's tiles, each counted by
    the function the kernel runs by."""
    q_blk = np.arange(-(-t // bq))
    if chunk is None:
        kv_idx = np.arange(-(-t // bk))
        hit = _tile_visible(causal, bd, q_blk[:, None], kv_idx[None, :], bq,
                            bk, offset, window, xp=np)
        return int(np.sum(hit)), hit.size
    runs = _chunk_runs(q_blk, bq, chunk, offset, t, window, bd, xp=np)
    return (int(sum(np.sum(hi - lo) for lo, hi, _ in runs)),
            q_blk.size * -(-t // chunk))


def causal_tiles(t: int, block_q: int, block_k: int, chunk=None,
                 offset: int = 0, **shape):
    """``(visited, total)`` of one head's causal forward over ``t``
    positions, from shapes alone (the routing manifest's
    ``causal_tiles_visited`` / ``causal_tiles_total``): pairs of (Q tile,
    compute chunk) where the kernels loop over chunks, else the grid's
    tiles, each counted by the predicate the kernel runs by. ``shape`` is
    what :func:`_tiling` weighs the forward's VMEM by (``d``, ``itemsize``,
    ``per_key``, ``per_q``)."""
    return window_tiles(t, None, block_q, block_k, chunk, offset, **shape)


def window_tiles(t: int, window: int, block_q: int, block_k: int, chunk=None,
                 offset: int = 0, **shape):
    """:func:`causal_tiles` under a ``window`` (``None``: the causal mask
    alone): ``(visited, total)`` of one head's forward over ``t`` positions
    of which a row sees the ``window`` keys up to its own (the routing
    manifest's ``window_tiles_visited`` / ``window_tiles_total``), by the
    predicates the kernels run by."""
    bq, bk, chunk = _tiling(t, t, block_q, block_k, chunk, True, None,
                            **shape)
    return _tiles_visited(t, bq, bk, chunk, True, None, offset, window)


def bd_tiles(seq: int, blk: int, block_q: int, block_k: int, chunk=None,
             **shape):
    """:func:`causal_tiles` of a block-diffusion row of ``2 * seq``
    positions in blocks of ``blk`` (the routing manifest's
    ``bd_tiles_visited`` / ``bd_tiles_total``)."""
    bd = (seq, blk)
    bq, bk, chunk = _tiling(2 * seq, 2 * seq, block_q, block_k, chunk, False,
                            bd, **shape)
    return _tiles_visited(2 * seq, bq, bk, chunk, False, bd)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _scores(q_ref, k_ref, bias_ref, segq_ref, segk_ref, q_blk, kv_blk, rows,
            *, scale: float, causal: bool, offset: int, block_q: int,
            block_k: int, tq: int, tk: int, bd=None, window=None,
            crossed: bool = True):
    """The scaled Q tile, ``rows`` of the resident K tile, and their masked
    scores: what all three kernels start a (Q tile, K tile or compute
    chunk) pair with. The mask knows the rows as block ``kv_blk`` of
    ``block_k`` keys; a pair that no edge of the mask crosses
    (``crossed=False`` from a chunk loop: every pair of it visible) takes
    neither the causal mask, nor the ``window``, nor the block-diffusion
    one."""
    if not crossed:
        causal, bd = False, None
    q = _zero_oob_rows(q_ref[0].astype(jnp.float32) * scale,
                       q_blk, block_q, tq)
    k = _zero_oob_rows(k_ref[0, rows].astype(jnp.float32), kv_blk, block_k,
                       tk)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    bias = None if bias_ref is None else bias_ref[0, rows].reshape(1, -1)
    seg_q = None if segq_ref is None else segq_ref[0]
    seg_k = None if segk_ref is None else segk_ref[0, rows]
    s = _mask_scores(s, q_blk, kv_blk, block_q=block_q, block_k=block_k,
                     tq=tq, tk=tk, causal=causal, offset=offset,
                     bias=bias, seg_q=seg_q, seg_k=seg_k, bd=bd,
                     window=window)
    return q, k, s


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref, o_ref,
                lse_ref, acc_ref=None, m_ref=None, l_ref=None, *,
                scale: float, causal: bool, offset: int, block_q: int,
                block_k: int, tq: int, tk: int, bd=None, chunk=None,
                window=None):
    scores = functools.partial(
        _scores, q_ref, k_ref, bias_ref, segq_ref, segk_ref,
        scale=scale, causal=causal, offset=offset, block_q=block_q, tq=tq,
        tk=tk, bd=bd, window=window)
    if chunk is not None:
        q_blk = pl.program_id(1)

        # One grid step holds every key: the online-softmax state is a
        # value carried round the loop of chunks, never in scratch (whose
        # read-modify-write a chunk costs more than the chunk's scores).
        def visit(state, ci, rows, crossed):
            m_prev, l_prev, acc = state
            _, _, s = scores(q_blk, ci, rows, crossed=crossed,
                             block_k=chunk)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            p = jnp.where(s > _NEG_INF / 2, p, 0.0)
            correction = jnp.exp(m_prev - m_new)
            v = _zero_oob_rows(v_ref[0, rows].astype(jnp.float32), ci,
                               chunk, tk)
            return (m_new, l_prev * correction + jnp.sum(p, axis=1),
                    acc * correction[:, None] +
                    jnp.dot(p, v, preferred_element_type=jnp.float32))

        m, l, acc = _chunk_loop(
            visit, (jnp.full((block_q,), _NEG_INF, jnp.float32),
                    jnp.zeros((block_q,), jnp.float32),
                    jnp.zeros(q_ref.shape[1:], jnp.float32)),
            q_blk, block_q, chunk, offset, tk, window, bd)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m + jnp.log(l_safe))[:, None]
        return

    kv_idx = pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_blk = pl.program_id(1)

    @pl.when(_tile_visible(causal, bd, q_blk, kv_idx, block_q, block_k,
                           offset, window))
    def _():
        _, _, s = scores(q_blk, kv_idx, slice(None), block_k=block_k)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        # A row with no visible key yet has m_new == _NEG_INF and s - m_new
        # == 0 → p would be 1; zero it so masked keys never contribute.
        p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * correction + jnp.sum(p, axis=1)
        v = _zero_oob_rows(v_ref[0].astype(jnp.float32), kv_idx, block_k, tk)
        acc_ref[:] = (acc_ref[:] * correction[:, None] +
                      jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_ref[:] = m_new

    @pl.when(kv_idx == num_kv - 1)
    def _():
        l = l_ref[:]
        # Rows with every key masked (all-padding keys, or ragged-edge rows
        # past tq whose stores are clipped) normalise to zero output.
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[:] + jnp.log(l_safe))[:, None]


def _per_key_spec(h: int, bk: int):
    # A (B, Tk, 1) per-key input (key_bias, k-side segment ids) — keys on
    # the sublane dim so the block is legal for exactly the block_k values
    # that are legal for K itself; grid axis 0 runs over batch*heads,
    # b // h broadcasts over the heads folded into it.
    return pl.BlockSpec((1, bk, 1), lambda b, i, j, h=h: (b // h, j, 0))


def _per_q_spec(h: int, bq: int):
    # A (B, Tq, 1) per-query input (q-side segment ids), following the
    # q tile.
    return pl.BlockSpec((1, bq, 1), lambda b, i, j, h=h: (b // h, i, 0))


_bias_spec = _per_key_spec
_seg_k_spec = _per_key_spec


def _fwd(q, k, v, bias, seg_q, seg_k, h, scale, causal, block_q, block_k,
         offset=0, bd=None, chunk=None, window=None):
    bh, tq, d = q.shape
    tk = k.shape[1]
    shape = _vmem_shape(d, q.dtype, bias, seg_q)
    bq, bk, chunk = _tiling(tq, tk, block_q, block_k, chunk, causal, bd,
                            kernel="fwd", **shape)
    grid = (bh, pl.cdiv(tq, bq), pl.cdiv(tk, bk))

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, offset=offset, block_q=bq,
        block_k=bk, tq=tq, tk=tk, bd=bd, chunk=chunk, window=window)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec(h, bk))
        args.append(bias)
    if seg_q is not None:
        # (B, T, 1) int32: this q tile's ids and the resident k tile's
        # ids (identical arrays single-device; the ring hands a rotated
        # k-side copy).
        in_specs.append(_per_q_spec(h, bq))
        in_specs.append(_per_key_spec(h, bk))
        args.append(seg_q)
        args.append(seg_k)
    kernel = _fill_optionals(kernel, bias is not None, seg_q is not None)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            # (…, 1) trailing lane dim keeps the block TPU-layout legal.
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        # the loop over compute chunks carries the softmax state itself
        scratch_shapes=[] if chunk is not None else [
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=_use_interpret(),
        name="flash_fwd",
        **_vmem_params(chunk, _vmem_need("fwd", bq, bk, chunk, **shape)),
    )
    with _tracing.scope("flash_attention"):
        o, lse = call(*args)
    return o, lse


def _fill_optionals(kernel, has_bias, has_seg):
    """Adapt the canonical (q, k, v, bias, segq, segk, *rest) kernel to a
    call signature where absent optional refs are not passed (pallas hands
    over exactly the refs named in in_specs)."""
    if has_bias and has_seg:
        return kernel

    @functools.wraps(kernel)
    def wrapped(q_ref, k_ref, v_ref, *rest):
        i = 0
        bias_ref = segq_ref = segk_ref = None
        if has_bias:
            bias_ref = rest[i]
            i += 1
        if has_seg:
            segq_ref, segk_ref = rest[i], rest[i + 1]
            i += 2
        return kernel(q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
                      *rest[i:])
    return wrapped


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _visit_pairs(visit, causal: bool, bd, q_blk, kv_idx, block_q: int,
                 block_k: int, chunk, offset: int, tk: int, window=None):
    """The backward kernels' ``visit(kv_blk, block_k, rows, crossed)`` over
    what the resident K tile holds that Q tile ``q_blk`` may see: the tile
    whole, if the grid-level skip lets it through, or with a compute chunk
    the loop over its chunks as far as the mask shows. Their sums live in
    scratch either way (there a chunk's read-modify-write is cheaper than
    carrying them round the loop)."""
    if chunk is None:
        pl.when(_tile_visible(causal, bd, q_blk, kv_idx, block_q, block_k,
                              offset, window))(
            lambda: visit(kv_idx, block_k, slice(None), True))
        return
    _chunk_loop(lambda _, ci, rows, crossed: visit(ci, chunk, rows, crossed),
                None, q_blk, block_q, chunk, offset, tk, window, bd)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
                   do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *,
                   scale: float, causal: bool, offset: int, block_q: int,
                   block_k: int, tq: int, tk: int, bd=None, chunk=None,
                   window=None):
    kv_idx = pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_blk = pl.program_id(1)

    def visit(kv_blk, block_k, rows, crossed):
        _, k, s = _scores(q_ref, k_ref, bias_ref, segq_ref, segk_ref, q_blk,
                          kv_blk, rows, scale=scale, causal=causal,
                          offset=offset, block_q=block_q, block_k=block_k,
                          tq=tq, tk=tk, bd=bd, window=window,
                          crossed=crossed)
        p = jnp.exp(s - lse_ref[0])
        p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        do = _zero_oob_rows(do_ref[0].astype(jnp.float32), q_blk, block_q, tq)
        v = _zero_oob_rows(v_ref[0, rows].astype(jnp.float32), kv_blk,
                           block_k, tk)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        # p == 0 entries must yield ds == 0 even when dp/delta hold clipped
        # garbage (0 * NaN != 0).
        ds = jnp.where(p > 0.0, p * (dp - delta_ref[0]), 0.0)
        acc_ref[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _visit_pairs(visit, causal, bd, q_blk, kv_idx, block_q, block_k, chunk,
                 offset, tk, window)

    @pl.when(kv_idx == num_kv - 1)
    def _():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
                    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, db_ref,
                    dk_acc, dv_acc, db_acc, dq_ref=None, dq_acc=None, *,
                    scale: float, causal: bool, offset: int, block_q: int,
                    block_k: int, tq: int, tk: int, bd=None, chunk=None,
                    window=None):
    """dK and dV of the resident K tile, summed over the grid's Q tiles.
    With ``dq_ref`` (the one-kernel backward: the K tile holds every key
    and the loop over its chunks meets all that this Q tile sees inside
    the grid step) also the Q tile's dQ, from the same ``ds``."""
    q_idx = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(q_idx == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if db_acc is not None:
            db_acc[:] = jnp.zeros_like(db_acc)

    if dq_acc is not None:
        dq_acc[:] = jnp.zeros_like(dq_acc)

    k_idx = pl.program_id(1)

    def visit(kv_blk, block_k, rows, crossed):
        q, k, s = _scores(q_ref, k_ref, bias_ref, segq_ref, segk_ref, q_idx,
                          kv_blk, rows, scale=scale, causal=causal,
                          offset=offset, block_q=block_q, block_k=block_k,
                          tq=tq, tk=tk, bd=bd, window=window,
                          crossed=crossed)
        p = jnp.exp(s - lse_ref[0])
        p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        do = _zero_oob_rows(do_ref[0].astype(jnp.float32), q_idx, block_q, tq)
        dv_acc[rows] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        v = _zero_oob_rows(v_ref[0, rows].astype(jnp.float32), kv_blk,
                           block_k, tk)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        # p == 0 entries must yield ds == 0 even when dp/delta hold clipped
        # garbage (0 * NaN != 0).
        ds = jnp.where(p > 0.0, p * (dp - delta_ref[0]), 0.0)
        dk_acc[rows] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        if db_acc is not None:
            # d(s)/d(bias) = 1 on visible entries → dbias_k = sum_q ds.
            db_acc[:] += jnp.sum(ds, axis=0)
        if dq_acc is not None:
            dq_acc[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _visit_pairs(visit, causal, bd, q_idx, k_idx, block_q, block_k, chunk,
                 offset, tk, window)

    if dq_acc is not None:
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    @pl.when(q_idx == num_q - 1)
    def _():
        # dk = dS^T (q*scale); q in this kernel already carries the scale.
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        if db_acc is not None:
            db_ref[0] = db_acc[:][:, None]


def _bwd(h, scale, causal, block_q, block_k, res, do, delta=None,
         offset=0, want_db=True, bd=None, chunk=None, window=None):
    q, k, v, bias, seg_q, seg_k, o, lse = res
    bh, tq, d = q.shape
    tk = k.shape[1]
    shape = _vmem_shape(d, q.dtype, bias, seg_q)
    track_db = bias is not None and want_db
    bq, bk, chunk = _tiling(tq, tk, block_q, block_k, chunk, causal, bd,
                            kernel="dkv", track_db=track_db, **shape)

    if delta is None:
        # delta_i = sum_d dO_i . O_i — the softmax-normalisation term of dS.
        # Ring callers precompute it once (it is invariant across ring hops).
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)

    common = dict(scale=scale, causal=causal, offset=offset, block_q=bq,
                  block_k=bk, tq=tq, tk=tk, bd=bd, chunk=chunk,
                  window=window)

    dkv_chunk, dkv_extra = _dkv_yields(chunk, track_db)
    # One kernel: where the dK/dV kernel loops over the chunks of a K tile
    # that holds every key, a Q tile meets all its keys inside one grid
    # step, and its dQ is summed there from the ``ds`` the step already
    # has. Everywhere else dQ has a kernel of its own.
    fused = dkv_extra == "dq"
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, **dict(common, chunk=dkv_chunk))

    def specs(order):
        # order: index_map arg order differs between the two kernels
        # (dq iterates kv innermost, dkv iterates q innermost).
        if order == "dq":
            qi = lambda b, i, j: (b, i, 0)
            ki = lambda b, i, j: (b, j, 0)
            qv = lambda b, i, j: (b, i, 0)
            bias_j = lambda b, i, j: j
        else:
            qi = lambda b, j, i: (b, i, 0)
            ki = lambda b, j, i: (b, j, 0)
            qv = lambda b, j, i: (b, i, 0)
            bias_j = lambda b, j, i: j
        sp = [
            pl.BlockSpec((1, bq, d), qi),
            pl.BlockSpec((1, bk, d), ki),
            pl.BlockSpec((1, bk, d), ki),
        ]
        if bias is not None:
            sp.append(pl.BlockSpec(
                (1, bk, 1), lambda *idx: (idx[0] // h, bias_j(*idx), 0)))
        if seg_q is not None:
            sp.append(pl.BlockSpec(
                (1, bq, 1), lambda *idx: (idx[0] // h, qi(*idx)[1], 0)))
            sp.append(pl.BlockSpec(
                (1, bk, 1), lambda *idx: (idx[0] // h, bias_j(*idx), 0)))
        sp += [
            pl.BlockSpec((1, bq, d), qv),
            pl.BlockSpec((1, bq, 1), qv),
            pl.BlockSpec((1, bq, 1), qv),
        ]
        return sp

    extra = () if bias is None else (bias,)
    if seg_q is not None:
        extra = extra + (seg_q, seg_k)
    if not track_db:
        # No db output/scratch: either there is no bias at all, or the
        # caller discards the mask-derived cotangent — keep the bias
        # INPUT (scores must mask) but skip the db work entirely. The
        # one-kernel backward has dQ's output and sum in their place.
        _dkv_canon = dkv_kernel

        def dkv_kernel(q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
                       do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *rest):
            dq_ref, dk_acc, dv_acc, dq_acc = (
                rest if fused else (None, *rest, None))
            return _dkv_canon(q_ref, k_ref, v_ref, bias_ref, segq_ref,
                              segk_ref, do_ref, lse_ref, delta_ref,
                              dk_ref, dv_ref, None, dk_acc, dv_acc, None,
                              dq_ref, dq_acc)
    dkv_kernel = _fill_optionals(dkv_kernel, bias is not None,
                                 seg_q is not None)

    dq_call = None if fused else pl.pallas_call(
        _fill_optionals(functools.partial(_bwd_dq_kernel, **common),
                        bias is not None, seg_q is not None),
        grid=(bh, pl.cdiv(tq, bq), pl.cdiv(tk, bk)),
        in_specs=specs("dq"),
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_use_interpret(),
        name="flash_dq",
        **_vmem_params(chunk, _vmem_need("dq", bq, bk, chunk, **shape)),
    )

    out_specs = [
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    scratch = [
        pltpu.VMEM((bk, d), jnp.float32),
        pltpu.VMEM((bk, d), jnp.float32),
    ]
    if track_db:
        # Per-(batch*head) bias gradient; heads are reduced below.
        out_specs.append(pl.BlockSpec((1, bk, 1),
                                      lambda b, j, i: (b, j, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, tk, 1), jnp.float32))
        scratch.append(pltpu.VMEM((bk,), jnp.float32))
    elif fused:
        out_specs.append(pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct(q.shape, q.dtype))
        scratch.append(pltpu.VMEM((bq, d), jnp.float32))

    dkv_need = _vmem_need("dkv", bq, bk, dkv_chunk, extra=dkv_extra, **shape)
    dkv_vmem = _vmem_params(dkv_chunk, dkv_need)
    dkv_call = pl.pallas_call(
        dkv_kernel,
        grid=(bh, pl.cdiv(tk, bk), pl.cdiv(tq, bq)),
        in_specs=specs("dkv"),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=_use_interpret(),
        name="flash_dkv",
        **dkv_vmem,
    )
    _tracing.note_routing(
        flash_bwd_kernels=1 if fused else 2,
        flash_bwd_vmem_bytes=dkv_need if dkv_vmem else 0)
    with _tracing.scope("flash_attention"):
        if not fused:
            dq = dq_call(q, k, v, *extra, do, lse, delta)
        outs = dkv_call(q, k, v, *extra, do, lse, delta)

    dbias = None
    if track_db:
        dk, dv, db = outs
        dbias = db.reshape(bh // h, h, tk, 1).sum(axis=1)
    elif fused:
        dk, dv, dq = outs
    else:
        dk, dv = outs
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(5, 17)))
def _flash(q, k, v, bias, seg, h, scale, causal, block_q, block_k,
           block_q_bwd, block_k_bwd, offset, bd, chunk, chunk_bwd, window):
    o, _ = _fwd(q, k, v, bias, seg, seg, h, scale, causal, block_q,
                block_k, offset=offset, bd=bd, chunk=chunk, window=window)
    return o


def _flash_fwd(q, k, v, bias, seg, h, scale, causal, block_q, block_k,
               block_q_bwd, block_k_bwd, offset, bd, chunk, chunk_bwd,
               window):
    o, lse = _fwd(q, k, v, bias, seg, seg, h, scale, causal, block_q,
                  block_k, offset=offset, bd=bd, chunk=chunk, window=window)
    # Named, so that a remat policy can keep them (models/remat.py) and
    # the backward does not run the forward kernel again to get them back.
    # The log-sum-exp is kept without its last dimension of 1, which the
    # chip's tiling pads to 128 lanes in HBM: 67 MB a call at 128 x 1024 for
    # 0.5 MB of numbers, and a step that holds 24 of them is slower for it.
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse[..., 0], RESIDUAL_NAMES[1])
    return o, (q, k, v, bias, seg, o, lse)


def _flash_bwd(h, scale, causal, block_q, block_k, block_q_bwd,
               block_k_bwd, offset, bd, chunk, chunk_bwd, window, res, do):
    # The backward kernels' VMEM profile differs from the forward's (two
    # extra fp32 accumulators per tile), so they may want their own tiles
    # — measured entries carry them (tile_table "tuned-*-fwdbwd").
    q, k, v, bias, seg, o, lse = res
    dq, dk, dv, dbias = _bwd(h, scale, causal, block_q_bwd, block_k_bwd,
                             (q, k, v, bias, seg, seg, o, lse[..., None]),
                             do, offset=offset, bd=bd, chunk=chunk_bwd,
                             window=window)
    # Integer segment ids take a symbolic-zero (float0) cotangent.
    dseg = (None if seg is None
            else np.zeros(seg.shape, dtype=jax.dtypes.float0))
    return dq, dk, dv, dbias, dseg


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False, scale: Optional[float] = None,
                    key_bias: Optional[jnp.ndarray] = None,
                    segment_ids: Optional[jnp.ndarray] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    causal_offset: int = 0,
                    block_diffusion: Optional[tuple] = None,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Fused attention ``softmax(q k^T * scale + key_bias [+ mask]) v``.

    Args:
      q: (batch, t_q, heads, head_dim).
      k, v: (batch, t_kv, heads, head_dim).
      causal: apply a causal mask (q position i attends to k positions <= i;
        requires t_q == t_kv).
      scale: logit scale; defaults to ``head_dim ** -0.5``.
      key_bias: optional (batch, t_kv) additive logit bias, broadcast over
        heads and queries — key-padding masks are ``where(pad, -1e30, 0)``,
        ALiBi-style learned biases also fit. Differentiated (the dK/dV
        kernel accumulates ``dbias_k = sum_q dS``).
      segment_ids: optional (batch, t) int — sequence-packing segment
        ids (self-attention: t_q == t_kv required); the kernels mask
        score tiles to same-segment (q, k) pairs, so packed documents
        cannot attend across boundaries at any sequence length.
      causal_offset: shifts the causal diagonal — visible iff
        ``i + causal_offset >= j`` (−1 = strict causal; used by striped
        ring layouts). Only meaningful with ``causal=True``.
      block_diffusion: optional static ``(seq_len, block_len)``: the rows
        are block-diffusion training rows ``[noisy ; clean]`` of
        ``2 * seq_len`` positions (``ops.attention.block_diffusion_mask``
        says who sees whom); the kernels mask score tiles by position and
        skip the tiles, or with the table's ``chunk`` the chunks of the
        resident keys, that hold no visible pair. Not with ``causal``.
      window: optional static number of keys a row sees, its own included
        (sliding-window attention; needs ``causal=True``): visible iff
        ``0 <= i + causal_offset - j < window``. The kernels mask the
        band's lower edge as they mask the diagonal and visit no tile or
        chunk that lies wholly under it. A window that holds every key a
        row can see is the causal mask and runs as it. Composes with
        ``key_bias`` and ``segment_ids``; not with ``block_diffusion``.
      block_q, block_k: tile sizes (clamped to the sequence lengths).
        ``None`` (default) consults the checked-in tile table
        (``ops/tile_table.py``; ``tools/tune_tiles.py`` measures it on
        the chip) for the nearest measured tiling of this (head_dim, seq,
        dtype, mask); with no entry the table's default (256, 512).
        Ragged edges are position-masked. A causal, window or
        block-diffusion entry may also carry a compute ``chunk``: the
        kernels then keep the whole key axis resident and loop inside a
        grid step over chunks of it as far as the mask shows (module
        docstring). The chunk is the table's to
        give and goes with the table's tiles only: a call that names its
        own tiles runs them whole.
      block_q_bwd, block_k_bwd: tile sizes for the backward (dQ and
        dK/dV) kernels, whose VMEM profile differs from the forward's.
        ``None`` consults the tile table (``tuned-*-fwdbwd`` entries from
        the forward + backward sweep carry measured values, and their
        own ``chunk_bwd``); entries without them fall back to the
        forward tiles. With a ``chunk_bwd`` the backward is one kernel:
        the dK/dV kernel's loop over the chunks of the resident K tile
        also sums dQ (module docstring).

    Returns (batch, t_q, heads, head_dim), same dtype as ``q``.
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if causal and tq != tk:
        raise ValueError(f"causal flash attention needs t_q == t_kv, "
                         f"got {tq} != {tk}")
    bd = None
    if block_diffusion is not None:
        bd = (int(block_diffusion[0]), int(block_diffusion[1]))
        if causal or tq != tk or tq != 2 * bd[0] or bd[0] % bd[1]:
            raise ValueError(
                f"block_diffusion={bd} needs non-causal self-attention "
                f"over 2 * seq_len positions in whole blocks, got "
                f"causal={causal}, t_q={tq}, t_kv={tk}")
    if window is not None:
        window = int(window)
        if not causal or bd is not None or window < 1:
            raise ValueError(
                f"window={window} needs causal=True, no block_diffusion "
                f"and at least one key, got causal={causal}, "
                f"block_diffusion={block_diffusion}")
        if window >= tq + causal_offset:    # no row has a key under it
            window = None
    scale = d ** -0.5 if scale is None else scale

    chunk = chunk_bwd = None
    if None in (block_q, block_k, block_q_bwd, block_k_bwd):
        from horovod_tpu.ops import tile_table
        kind = ("block_diffusion" if bd is not None
                else "window" if window is not None
                else "causal" if causal else "full")
        tq_, tk_, tqb_, tkb_, chunk_, chunk_bwd_ = tile_table.lookup_full(
            d, max(tq, tk), q.dtype, kind)
        block_q = tq_ if block_q is None else block_q
        block_k = tk_ if block_k is None else block_k
        # Explicit fwd tiles with no explicit bwd tiles: share the fwd
        # tiles (pre-r5 behavior) rather than mixing the caller's fwd
        # choice with a table bwd entry tuned for different fwd tiles.
        fwd_is_tables = (tq_, tk_) == (block_q, block_k)
        if block_q_bwd is None:
            block_q_bwd = tqb_ if fwd_is_tables else block_q
        if block_k_bwd is None:
            block_k_bwd = tkb_ if fwd_is_tables else block_k
        # A compute chunk was measured inside the table's tile, and goes
        # with it only.
        if fwd_is_tables:
            chunk = chunk_
        if (tqb_, tkb_) == (block_q_bwd, block_k_bwd):
            chunk_bwd = chunk_bwd_
    return _attend(q, k, v, causal, scale, key_bias, segment_ids,
                   (block_q, block_k, block_q_bwd, block_k_bwd, chunk,
                    chunk_bwd), causal_offset, bd, window)


def _attend(q, k, v, causal, scale, key_bias, segment_ids, tiles,
            causal_offset=0, bd=None, window=None):
    """:func:`flash_attention` once the tiles are settled: ``tiles`` is
    ``(block_q, block_k, block_q_bwd, block_k_bwd, chunk, chunk_bwd)`` as
    ``tile_table.lookup_full`` gives them. The tile sweep
    (``autotune_flash_blocks``) enters here, since a compute chunk is the
    table's to give and no argument of the public call."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_q, block_k, block_q_bwd, block_k_bwd, chunk, chunk_bwd = tiles

    # (B, T, H, D) -> (B*H, T, D): each grid row owns one head's sequence.
    def pack(x):
        t = x.shape[1]
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[3])

    if key_bias is not None:
        if key_bias.shape != (b, tk):
            raise ValueError(f"key_bias must be (batch, t_kv) = ({b}, {tk}), "
                             f"got {key_bias.shape}")
        key_bias = key_bias.astype(jnp.float32).reshape(b, tk, 1)
    seg = None
    if segment_ids is not None:
        if tq != tk:
            raise ValueError("segment_ids require self-attention shapes "
                             f"(t_q == t_kv), got {tq} != {tk}")
        if segment_ids.shape != (b, tq):
            raise ValueError(f"segment_ids must be (batch, t) = "
                             f"({b}, {tq}), got {segment_ids.shape}")
        seg = segment_ids.astype(jnp.int32).reshape(b, tq, 1)

    with _tracing.scope("flash/layout"):
        q, k, v = pack(q), pack(k), pack(v)
    o = _flash(q, k, v, key_bias, seg, h, float(scale),
               bool(causal), int(block_q), int(block_k),
               int(block_q_bwd), int(block_k_bwd), int(causal_offset), bd,
               chunk and int(chunk), chunk_bwd and int(chunk_bwd), window)
    if bd is not None:
        visited, total = bd_tiles(
            bd[0], bd[1], int(block_q), int(block_k), chunk,
            **_vmem_shape(d, q.dtype, key_bias, seg))
        _tracing.note_routing(bd_tiles_visited=visited,
                              bd_tiles_total=total)
    elif window is not None:
        visited, total = window_tiles(
            tq, window, int(block_q), int(block_k), chunk,
            int(causal_offset), **_vmem_shape(d, q.dtype, key_bias, seg))
        _tracing.note_routing(window_tiles_visited=visited,
                              window_tiles_total=total)
    elif causal:
        visited, total = causal_tiles(
            tq, int(block_q), int(block_k), chunk, int(causal_offset),
            **_vmem_shape(d, q.dtype, key_bias, seg))
        _tracing.note_routing(causal_tiles_visited=visited,
                              causal_tiles_total=total)
    with _tracing.scope("flash/layout"):
        return o.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
