"""Sequence-packed GPT-2 pretraining — many documents per row, exactly.

The standard long-context data format: variable-length documents are
packed back-to-back into fixed-length rows (no padding waste).
``segment_ids`` block attention across document boundaries on every
attention impl (the pallas flash kernels mask score tiles to same-segment
pairs), ``packed_positions`` restarts position ids per document, and
``loss_fn(..., segment_ids=)`` drops the cross-boundary targets — so
packing is EXACT: each packed document trains as if it were alone.

Run (single device or dp):
  JAX_PLATFORMS=cpu python examples/gpt2_packed.py --steps 3
Add --flash for the fused pallas kernel (interpreter-mode on CPU).
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn
from horovod_tpu.ops.attention import packed_positions


# Packing is a library utility: first-fit-decreasing row assignment
# (native C++ hvd_pack_ffd when available) + filler tokens with DISTINCT
# negative segment ids, so the packed loss drops every filler target and
# "never trains on filler" is literally true. See data/packing.py.
from horovod_tpu.data import pack_documents


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--row-len", type=int, default=128)
    ap.add_argument("--flash", action="store_true")
    args = ap.parse_args()

    hvd.init()
    cfg = dataclasses.replace(
        GPT2Config.tiny(), max_seq_len=args.row_len,
        attention="flash" if args.flash else "dense")
    model = GPT2(cfg)

    # Synthetic corpus: documents of wildly different lengths.
    rng = np.random.default_rng(0)
    docs = [rng.integers(1, cfg.vocab_size, rng.integers(8, 60)).tolist()
            for _ in range(12)]
    tokens, seg = pack_documents(docs, args.row_len)
    tokens, seg = jnp.asarray(tokens), jnp.asarray(seg)
    pos = packed_positions(seg)
    if hvd.rank() == 0:
        n_docs = int(seg.max()) + 1
        print(f"packed {n_docs} segments into {tokens.shape[0]} rows of "
              f"{args.row_len} tokens", flush=True)

    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    opt = hvd.DistributedOptimizer(optax.adamw(3e-3))
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss(p):
            logits = model.apply({"params": p}, tokens,
                                 segment_ids=seg, positions=pos)
            return loss_fn(logits, tokens, segment_ids=seg)
        l, grads = jax.value_and_grad(loss)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, l

    first = last = None
    for i in range(args.steps):
        params, opt_state, l = step(params, opt_state)
        last = float(l)
        first = first if first is not None else last
        print(f"step {i}: packed loss {last:.4f}", flush=True)

    # The exactness claim, demonstrated: document 0's logits inside the
    # packed row equal running it alone (FFD may have placed it in any
    # row/offset — locate it by its segment id).
    rr, cc = np.where(np.asarray(seg) == 0)
    row, c0, c1 = int(rr[0]), int(cc.min()), int(cc.max()) + 1
    d0 = tokens[row, c0:c1][None]
    got = model.apply({"params": params}, tokens,
                      segment_ids=seg, positions=pos)[row, c0:c1]
    alone = model.apply({"params": params}, d0)[0]
    err = float(jnp.abs(got - alone).max())
    print(f"packed-vs-alone max logit diff: {err:.2e}", flush=True)
    assert err < 5e-2, err
    if args.steps > 1:
        assert last < first, "loss did not decrease"


if __name__ == "__main__":
    main()
