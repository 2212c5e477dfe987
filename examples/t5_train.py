"""T5 encoder-decoder seq2seq training with dp x tp sharding (upstream's
role here is its framework-native example scripts, ``horovod/examples``;
this completes the zoo's architecture classes next to the decoder-only
and encoder-only examples).

The synthetic task is learnable: the target is the source reversed, so
cross-attention has real structure to find. Padding exercises both mask
paths (encoder self-attn + cross-attn ignore source pads; pad labels
carry no loss).

Run (single device or the virtual CPU mesh):
  JAX_PLATFORMS=cpu python examples/t5_train.py --steps 5
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.t5 import (T5, T5Config, partition_rules,
                                   seq2seq_loss)
from horovod_tpu.parallel import make_mesh, shard_pytree


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel size (default: 2 if it divides "
                         "the world, else 1)")
    ap.add_argument("--seq", type=int, default=24)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()

    hvd.init()
    n = hvd.size()
    tp = args.tp if args.tp is not None else (2 if n % 2 == 0 else 1)
    if n % tp:
        raise SystemExit(f"--tp {tp} must divide world size {n}")
    dp = n // tp
    mesh = make_mesh({"dp": dp, "tp": tp})

    cfg = T5Config.tiny()
    model = T5(cfg)
    rng = np.random.default_rng(0)
    # Reversal task with ragged source lengths -> real padding.
    B = args.batch * dp
    src = np.full((B, args.seq), cfg.pad_id, np.int64)
    tgt = np.full((B, args.seq), cfg.pad_id, np.int64)
    for b in range(B):
        ln = rng.integers(args.seq // 2, args.seq + 1)
        row = rng.integers(1, cfg.vocab_size, ln)
        src[b, :ln] = row
        tgt[b, :ln] = row[::-1]
    src, tgt = jnp.asarray(src, jnp.int32), jnp.asarray(tgt, jnp.int32)

    from horovod_tpu.models.t5 import shift_right
    params = model.init(jax.random.PRNGKey(0), src,
                        shift_right(tgt, cfg.pad_id))["params"]
    params = shard_pytree(params, mesh, partition_rules())
    src = jax.device_put(src, NamedSharding(mesh, P("dp")))
    tgt = jax.device_put(tgt, NamedSharding(mesh, P("dp")))

    opt = hvd.DistributedOptimizer(optax.adamw(3e-3))
    opt_state = opt.init(params)

    def train_step(params, opt_state, src, tgt):
        l, grads = jax.value_and_grad(
            lambda p: seq2seq_loss(model, p, src, tgt))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, l

    with jax.set_mesh(mesh):
        step = jax.jit(train_step, donate_argnums=(0, 1))
        first = l = None
        for i in range(args.steps):
            params, opt_state, l = step(params, opt_state, src, tgt)
            l = float(l)
            first = first if first is not None else l
            print(f"step {i}: loss {l:.4f}", flush=True)
    if hvd.rank() == 0 and l is not None:
        print(f"final seq2seq loss {l:.4f} (first {first:.4f}) over "
              f"dp={dp} tp={tp}")
        if args.steps > 1:
            assert l < first, "loss did not decrease"


if __name__ == "__main__":
    main()
