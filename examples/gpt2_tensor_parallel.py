"""GPT-2 with dp x tp sharding (reference config "GPT-2 medium,
tensor-fusion stress"): Megatron-style partition rules + GSPMD — XLA inserts
the collectives the reference's NCCL stack would issue by hand.
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
from horovod_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn, partition_rules
from horovod_tpu.parallel import make_mesh, shard_pytree


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    hvd.init()
    n = hvd.size()
    tp = min(args.tp, n)
    mesh = make_mesh({"dp": n // tp, "tp": tp})
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    cfg = GPT2Config(vocab_size=512, max_seq_len=args.seq,
                     num_layers=args.layers, num_heads=4,
                     d_model=args.d_model)
    model = GPT2(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, args.seq)),
                         jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    params = shard_pytree(params, mesh, partition_rules())
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))

    opt = hvd.DistributedOptimizer(optax.adamw(3e-4))
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, tokens):
        l, g = jax.value_and_grad(
            lambda p: loss_fn(model.apply({"params": p}, tokens), tokens))(
            params)
        updates, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, l

    for i in range(args.steps):
        params, opt_state, l = step(params, opt_state, tokens)
        print(f"step {i}: loss={float(l):.4f}")


if __name__ == "__main__":
    main()
