"""Llama-family training (RoPE + RMSNorm + SwiGLU + GQA) with dp x tp
sharding — the modern-LLM analogue of the reference's framework-native
example scripts (upstream horovod/examples): Megatron partition rules +
GSPMD insert the collectives, GQA keeps the kv parameter/optimizer
footprint at num_kv_heads/num_heads of MHA.
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
from horovod_tpu.models.llama import (
    Llama, LlamaConfig, loss_fn, partition_rules,
)
from horovod_tpu.parallel import make_mesh, shard_pytree


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    hvd.init()
    n = hvd.size()
    if n % args.tp:
        raise SystemExit(f"--tp {args.tp} must divide world size {n}")
    dp = n // args.tp
    mesh = make_mesh({"dp": dp, "tp": args.tp})

    cfg = LlamaConfig(vocab_size=256, max_seq_len=args.seq,
                      num_layers=args.layers, num_heads=args.heads,
                      num_kv_heads=args.kv_heads, d_model=args.d_model,
                      d_ff=2 * args.d_model)
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch * dp, args.seq)),
        jnp.int32)

    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    params = shard_pytree(params, mesh, partition_rules())
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp")))

    opt = hvd.DistributedOptimizer(optax.adamw(3e-3))
    opt_state = opt.init(params)

    def train_step(params, opt_state, tokens):
        l, grads = jax.value_and_grad(
            lambda p: loss_fn(model.apply({"params": p}, tokens),
                              tokens))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, l

    with jax.set_mesh(mesh):
        step = jax.jit(train_step, donate_argnums=(0, 1))
        first = l = None
        for i in range(args.steps):
            params, opt_state, l = step(params, opt_state, tokens)
            l = float(l)
            first = first if first is not None else l
            print(f"step {i}: loss {l:.4f}", flush=True)
    if hvd.rank() == 0 and l is not None:
        kv_frac = cfg.num_kv_heads / cfg.num_heads
        print(f"final loss {l:.4f} (first {first:.4f}); "
              f"GQA kv heads at {kv_frac:.0%} of MHA")
        if args.steps > 1:
            assert l < first, "loss did not decrease"


if __name__ == "__main__":
    main()
