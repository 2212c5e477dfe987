#!/usr/bin/env python
"""Regenerate the flash-attention tile table from on-device measurements.

Sweeps a grid of attention shapes through ``autotune_flash_blocks`` and
records each winner into ``horovod_tpu/ops/flash_tiles.json`` (the table
``flash_attention`` consults by default — see ``ops/tile_table.py``).

Run on a real TPU:  python tools/tune_tiles.py [--quick | --fwdbwd]
                        [--shape HEAD_DIMxSEQ] [--out PATH]

``--quick`` uses fwd-only chain=2 probes: differentiated pallas chains
compile per candidate, so the full sweep is the slow one. Shapes cover the
model zoo: GPT-2 (d64 causal @1024), BERT (d64 full @512), long-context
(d64/d128 @4096/8192), and the per-hop ring shard shapes. Off-TPU the
kernels run in the Pallas interpreter and a timing means nothing, so the
tool refuses to start.

How the block-diffusion entry (head 128, 8,192 positions ``[noisy ;
clean]``) was made: ``python tools/tune_tiles.py --fwdbwd --shape 128x8192
--out chiprun_out/t.json`` on the v5e (~2.5 min, every candidate printed),
the winner copied into the shipped table with the PR in its ``source``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# Runnable from any cwd.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (head_dim, seq, batch, heads, causal, kind, dtype[, window or block]); a
# "window" shape is causal under a sliding window of that many keys, a
# "block_diffusion" shape is ``seq`` positions [noisy ; clean] in blocks of
# that many.
# Ring probes run causal=False: all but one of a ring's n hops carry
# fully-unmasked blocks (the causal mask only bites near the diagonal hop),
# so the unmasked kernel is the representative per-hop workload — a causal
# probe would skip ~half the KV blocks and crown tiles tuned for the
# wrong grid-overhead/VMEM balance.
SHAPES = [
    (64, 1024, 8, 12, True, "causal", "bfloat16"),   # GPT-2 base
    (64, 512, 8, 12, False, "full", "bfloat16"),     # BERT-large class
    (64, 4096, 2, 12, True, "causal", "bfloat16"),   # long context
    (128, 2048, 2, 16, True, "causal", "bfloat16"),  # wide-head LLM class
    (64, 1024, 2, 12, False, "ring", "bfloat16"),    # ring per-hop shard
    (64, 2048, 2, 12, False, "ring", "bfloat16"),
    # r5 coverage growth (the r4 table had 6 bf16 shapes and nothing
    # else — VERDICT r4 weak #2): 8k context, d=256 wide heads, fp32.
    (64, 8192, 1, 12, True, "causal", "bfloat16"),   # 8k long context
    (256, 2048, 1, 8, True, "causal", "bfloat16"),   # d256 head class
    (64, 1024, 8, 12, True, "causal", "float32"),    # fp32 training
]

# Shapes worth the much costlier differentiated-kernel (phase-2 backward)
# sweep: the three configs the zoo's headline numbers actually run.
FWDBWD_SHAPES = [
    (64, 1024, 8, 16, True, "causal", "bfloat16"),   # GPT-2 medium @1k
    (64, 512, 8, 12, False, "full", "bfloat16"),     # BERT @512
    (64, 4096, 2, 12, True, "causal", "bfloat16"),   # GPT-2 @4k
    (64, 8192, 4, 32, True, "causal", "bfloat16"),   # LFM2 hybrid @8k
    (256, 8192, 2, 20, True, "causal", "bfloat16"),  # latent attention @8k
    (128, 16384, 2, 28, True, "causal", "bfloat16"),  # global layer @16k
    (128, 16384, 2, 28, True, "window", "bfloat16", 4096),  # SWA 4,096 @16k
    (128, 8192, 2, 32, False, "block_diffusion", "bfloat16", 4),  # SDAR 2x4k
]

# What the --fwdbwd sweep tries on a causal shape besides the plain grid:
# (block_q, chunk) with the K tile the whole key axis, resident, and the
# kernels looping inside a grid step over chunks of it as far as the
# diagonal, so that the scores above it are not computed and the chunks
# under it take no mask. As a backward tiling such a candidate is the
# one-kernel backward (dQ summed in the dK/dV kernel's loop); the kernels
# ask for the VMEM a resident tile takes (flash_attention._vmem_need), so
# at 8,192 keys these compile, at head 256 in the forward too. Under a
# window the loop also starts at the band's lower edge; under the
# block-diffusion mask it takes a noisy Q tile's own chunk and the clean
# prefix (flash_attention._bd_chunks), and a chunk of 256 is tried too:
# the chunk on the noisy diagonal is nearly all masked whatever its size.
CAUSAL_CHUNKED = [(128, 128), (128, 256), (256, 128), (256, 256), (256, 512),
                  (512, 256), (512, 512)]
# At 4k and beyond the grid's K axis has many steps to skip, a tile may be
# larger than FLASH_TILE_CANDIDATES goes, and nothing 128 wide has ever
# come near winning (PERF.md section 6, PRs 30, 31, 33): there the sweep
# is over these.
LONG_TILES = [(512, 512), (512, 1024), (1024, 512), (1024, 1024),
              (512, 2048)]
LONG_CHUNKED = [(256, 512), (512, 512), (256, 1024), (512, 1024),
                (1024, 512), (1024, 1024)]
BD_CHUNKED = LONG_CHUNKED + [(256, 256), (512, 256)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fwd-only chain=2 probes (minutes, not an hour)")
    ap.add_argument("--fwdbwd", action="store_true",
                    help="two-phase backward sweep over FWDBWD_SHAPES: "
                         "fwd winner from cheap fwd-only probes, then "
                         "each candidate re-timed as the backward tiling "
                         "(writes block_q_bwd/block_k_bwd, source "
                         "tuned-*-fwdbwd)")
    ap.add_argument("--shape", default=None, metavar="HEAD_DIMxSEQ",
                    help="only the shapes of this head size and length "
                         "(64x1024), not the whole list")
    ap.add_argument("--out", default=None,
                    help="alternate table path (default: shipped table)")
    args = ap.parse_args(argv)

    import jax
    from horovod_tpu.autotune import (FLASH_TILE_CANDIDATES,
                                      autotune_flash_blocks)
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()
    backend = jax.default_backend()
    print(f"backend={backend} device={jax.devices()[0].device_kind}")
    if backend != "tpu":
        print("tune_tiles: not a TPU — the kernels would run in the Pallas "
              "interpreter and every timing would be noise; nothing was "
              "measured.", file=sys.stderr)
        return 2

    if args.fwdbwd:
        # Phase 1 fwd-only (cheap compiles) picks the fwd tiles; phase 2
        # pays the differentiated-kernel compile per candidate — only for
        # the shapes the headline numbers run.
        shapes = FWDBWD_SHAPES
        kw = dict(include_backward=False, chain=8, steps_per_trial=5,
                  tune_backward=True)
    else:
        shapes = SHAPES
        kw = dict(include_backward=not args.quick,
                  chain=2 if args.quick else 8,
                  steps_per_trial=3 if args.quick else 5)
    if args.shape:
        shapes = [s for s in shapes if f"{s[0]}x{s[1]}" == args.shape]
    failed = 0
    for head_dim, seq, batch, heads, causal, kind, dtype, *extra in shapes:
        shape = (batch, seq, heads, head_dim)
        window = extra[0] if kind == "window" else None
        bd = (seq // 2, extra[0]) if kind == "block_diffusion" else None
        t0 = time.time()
        candidates = None
        if args.fwdbwd and kind in ("causal", "window", "block_diffusion"):
            long = seq >= 4096
            candidates = LONG_TILES if long else [
                c for c in FLASH_TILE_CANDIDATES if c[1] <= seq]
            chunked = (BD_CHUNKED if bd else LONG_CHUNKED if long
                       else CAUSAL_CHUNKED)
            candidates = candidates + [(bq, seq, chunk)
                                       for bq, chunk in chunked]
        try:
            best, trials = autotune_flash_blocks(
                shape, dtype=dtype, causal=causal, record=True,
                candidates=candidates, record_kind=kind,
                record_path=args.out, window=window, block_diffusion=bd,
                **kw)
        except Exception as e:   # one bad shape must not kill the sweep
            print(f"  {kind} d{head_dim} T{seq} {dtype}: FAILED ({e})")
            failed += 1
            continue
        n_timed = len([k for k in trials if k[0] != "bwd"])
        print(f"  {kind}{extra[0] if extra else ''} d{head_dim} T{seq} "
              f"{dtype}: best={best} "
              f"({n_timed} fwd candidates, {time.time() - t0:.0f}s)")
        # every candidate, the losers too: phase 1 is the forward alone,
        # "bwd" rows are forward + backward with the forward at its winner
        for cand, secs in trials.items():
            print(f"    {cand}: {secs * 1e6:.1f} us/call")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
