"""Long-context GPT-2 with striped ring attention (sequence parallelism).

The north-star long-context recipe (SURVEY §2 row 24) end-to-end: a
sequence far beyond one device's attention budget is sharded over the
``sp`` mesh axis in the **striped** layout (shard r holds global positions
r, r+n, r+2n, ... — Striped Attention), attention runs as a ring of
per-block computations with K/V hopping shard-to-shard via ``ppermute``,
and the loss is ``striped_lm_loss`` — exact over every next-token pair,
including the shard boundaries a contiguous per-shard shift would drop.

Run (8 virtual devices, T_global = 2048):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/gpt2_long_context.py --steps 3
On a TPU slice the same script rides ICI; add --flash for the pallas
flash kernel per ring block (interpreter-mode on CPU: slow but exact).
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seq-len", type=int, default=2048,
                    help="GLOBAL sequence length (sharded over sp)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--flash", action="store_true",
                    help="pallas flash kernel per ring block")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config, striped_lm_loss

    hvd.init(axis_name="sp")
    n = hvd.size()
    T = args.seq_len
    assert T % n == 0, f"--seq-len must divide over {n} shards"

    cfg = GPT2Config(vocab_size=512, max_seq_len=T, num_layers=2,
                     num_heads=4, d_model=128, dtype=jnp.float32,
                     use_ring_attention=True, ring_layout="striped",
                     attention="flash" if args.flash else "dense")
    model = GPT2(cfg)

    rng = np.random.default_rng(0)
    tokens_global = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, T)), jnp.int32)
    # Striped layout: shard r must hold positions r, r+n, r+2n, ... — lay
    # the sequence out stride-major so shard_map's contiguous split does it.
    striped = tokens_global.reshape(args.batch, T // n, n) \
        .swapaxes(1, 2).reshape(args.batch, T)

    # Param init traces no ring ops: use the plain config on a short stub.
    params = GPT2(GPT2Config(
        vocab_size=cfg.vocab_size, max_seq_len=T, num_layers=2,
        num_heads=4, d_model=128, dtype=jnp.float32)).init(
            jax.random.PRNGKey(0), tokens_global[:, :8])

    opt = hvd.DistributedOptimizer(optax.adamw(args.lr))
    opt_state = opt.init(params["params"])

    def step(params, opt_state, toks):
        def loss_fn(p):
            logits = model.apply({"params": p}, toks)
            return striped_lm_loss(logits, toks)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2, loss

    spmd_step = hvd.spmd(step,
                         in_specs=(P(), P(), P(None, "sp")),
                         out_specs=(P(), P(), P()))

    losses = []
    p = params["params"]
    for i in range(args.steps):
        p, opt_state, loss = spmd_step(p, opt_state, striped)
        losses.append(float(loss))
        print(f"step {i}: loss {losses[-1]:.4f} "
              f"(T={T} over {n} sp shards, {T // n}/shard)")
    assert losses[-1] < losses[0], losses
    print("OK")


if __name__ == "__main__":
    main()
