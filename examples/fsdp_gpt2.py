"""FSDP / ZeRO-3 GPT-2 training (the DeepSpeed-ZeRO-3-on-hvd role,
TPU-native): transformer blocks stored as 1/n flat shards per device,
gathered just in time inside the layer scan, gradients leaving each block
as one fused psum_scatter, and a shard-domain AdamW that never
all-gathers updates — peak parameter memory is |params|/n + one block.

Run:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/fsdp_gpt2.py --steps 5
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--layers", type=int, default=4)
    args = ap.parse_args()

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.gpt2 import GPT2, Block, GPT2Config, loss_fn
    from horovod_tpu.optimizer_sharded import ShardedAdamWState
    from horovod_tpu.parallel.fsdp import (flat_size, fsdp_adamw,
                                           fsdp_scan_blocks,
                                           stack_layer_shards)

    hvd.init()
    n = hvd.size()
    cfg = GPT2Config(vocab_size=256, max_seq_len=64,
                     num_layers=args.layers, num_heads=4, d_model=64,
                     dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (n, 4, 32)),
                         jnp.int32)

    params = GPT2(cfg).init(jax.random.PRNGKey(0),
                            tokens.reshape(-1, 32))["params"]
    layer_keys = sorted((k for k in params if k.startswith("h")),
                        key=lambda k: int(k[1:]))
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[params[k] for k in layer_keys])
    rest = {k: v for k, v in params.items() if not k.startswith("h")}
    rows = stack_layer_shards(stacked)
    template = params[layer_keys[0]]
    total = flat_size(stacked)
    print(f"{total:,} block params stored as {rows.shape} "
          f"({rows.size // n:,} per device — 1/{n})")

    block = Block(cfg)
    ln_f = nn.LayerNorm(dtype=jnp.float32)
    opt = fsdp_adamw(1e-3)
    state = opt.init(rows.reshape(-1))

    def step(rows, mu, nu, stepc, rest, toks):
        def loss(rows):
            T = toks.shape[-1]
            h = (rest["wte"][toks[0]].astype(cfg.dtype)
                 + rest["wpe"][jnp.arange(T)].astype(cfg.dtype))
            h = fsdp_scan_blocks(
                lambda p, hh: block.apply({"params": p}, hh),
                template, rows, h)
            h = ln_f.apply({"params": rest["ln_f"]}, h)
            logits = jnp.einsum("btd,vd->btv", h.astype(jnp.float32),
                                rest["wte"])
            return loss_fn(logits, toks[0])

        l, g_rows = jax.value_and_grad(loss)(rows)
        L = g_rows.shape[0]
        upd, st2 = opt.update(g_rows.reshape(-1),
                              ShardedAdamWState(stepc, mu, nu),
                              rows.reshape(-1))
        return (rows + upd.reshape(L, -1), st2.mu, st2.nu, st2.step,
                jax.lax.pmean(l, "hvd"))

    fn = hvd.spmd(step,
                  in_specs=(P(None, "hvd"), P("hvd"), P("hvd"),
                            P("hvd"), P(), P("hvd")),
                  out_specs=(P(None, "hvd"), P("hvd"), P("hvd"),
                             P("hvd"), P()))

    mu, nu, stepc = state.mu, state.nu, state.step
    losses = []
    for i in range(args.steps):
        rows, mu, nu, stepc, l = fn(rows, mu, nu, stepc, rest, tokens)
        losses.append(float(l))
        print(f"step {i}: loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], losses
    print("FSDP OK: loss decreased with 1/n-sharded parameters")


if __name__ == "__main__":
    main()
