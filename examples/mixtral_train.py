"""Mixtral-style MoE training: Llama blocks with top-2-routed SwiGLU
experts sharded over the ep mesh axis (upstream's role here is its
framework-native example scripts, ``horovod/examples``; experts-on-hvd
is the DeepSpeed-MoE layering the reference ecosystem uses).

dp x ep x tp: the router's dispatch/combine einsums contract a
token-sharded axis against expert-sharded weights, which is exactly
where GSPMD inserts the expert all-to-alls — no hand-written
communication. The aux load-balance loss comes back through the sown
"losses" collection (``loss_fn_moe``).

Run (single device or the virtual CPU mesh):
  JAX_PLATFORMS=cpu python examples/mixtral_train.py --steps 3
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
    Llama, LlamaConfig, loss_fn_moe, partition_rules,
)
from horovod_tpu.parallel import make_mesh, shard_pytree


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--ep", type=int, default=None,
                    help="expert-parallel size (default: 2 if it divides "
                         "the world, else 1)")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    hvd.init()
    n = hvd.size()
    ep = args.ep if args.ep is not None else (2 if n % 2 == 0 else 1)
    if n % (ep * args.tp):
        raise SystemExit(f"ep*tp {ep * args.tp} must divide world {n}")
    dp = n // (ep * args.tp)
    mesh = make_mesh({"dp": dp, "ep": ep, "tp": args.tp})

    cfg = LlamaConfig.tiny(num_experts=args.experts,
                           max_seq_len=args.seq)
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
            lambda p: loss_fn_moe(model, p, tokens))(params)
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
        n_expert_params = sum(
            int(np.prod(v.shape))
            for path, v in jax.tree_util.tree_leaves_with_path(params)
            if "/".join(str(k.key) for k in path).endswith(
                ("w_gate", "w_in", "w_out")))
        print(f"final loss {l:.4f} (first {first:.4f}); "
              f"{args.experts} SwiGLU experts, top-2 routed, "
              f"{n_expert_params:,} expert params over ep={ep}")
        if args.steps > 1:
            assert l < first, "loss did not decrease"


if __name__ == "__main__":
    main()
