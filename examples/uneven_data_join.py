"""Uneven per-rank data with the mask-based Join (upstream ``hvd.join``'s
purpose, the SPMD way): every rank runs the step loop to the MAX step
count; ranks that have exhausted their data pass ``alive=0`` so they
contribute zero gradients and the mean divides by the live count — exactly
upstream's joined-rank-contributes-nothing semantics, but inside one jitted
program (no controller, no early exit).

Run (virtual 8-device CPU mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/uneven_data_join.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    hvd.init()
    n = hvd.size()

    # Rank r has (r+1) * 4 batches — genuinely uneven data.
    rng = np.random.default_rng(0)
    per_rank_batches = [(r + 1) * 4 for r in range(n)]
    max_steps = min(args.steps, max(per_rank_batches))
    print("batches per rank:", per_rank_batches, "running", max_steps,
          "steps")

    X = jnp.asarray(rng.standard_normal((n, max_steps, 16, 4)), jnp.float32)
    true_w = jnp.asarray([[1.0], [-2.0], [0.5], [3.0]])
    Y = jnp.einsum("rsbf,fo->rsbo", X, true_w)[..., 0] + 0.1
    limits = jnp.asarray(per_rank_batches, jnp.int32)

    W = jnp.zeros((4, 1))
    b = jnp.zeros((1,))
    # The gradient sync is the explicit masked allreduce below, so the
    # inner optimizer stays plain (DistributedOptimizer would reduce again).
    opt = optax.sgd(0.1)
    opt_state = opt.init((W, b))

    def train_step(params, opt_state, x, y, limit, step):
        W, b = params

        def loss_fn(Wb):
            W, b = Wb
            pred = x @ W + b[None]
            return jnp.mean((pred[..., 0] - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)((W, b))
        # The Join: this rank is alive while it still has data. Dead ranks
        # contribute zeros; the mean divides by the live count.
        alive = (step < limit).astype(jnp.float32)
        grads = hvd.allreduce_gradients(grads, alive=alive)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                loss[None], alive[None])

    def body(params, opt_state, X, Y, limits, step):
        return train_step(params, opt_state, X[0, step], Y[0, step],
                          limits[0], step)

    fn = hvd.spmd(body,
                  in_specs=(P(), P(), P("hvd"), P("hvd"), P("hvd"), P()),
                  out_specs=(P(), P(), P("hvd"), P("hvd")))
    params = (W, b)
    for step in range(max_steps):
        params, opt_state, loss, alive = fn(params, opt_state, X, Y, limits,
                                            jnp.int32(step))
        live = int(np.asarray(alive).sum())
        print(f"step {step:2d}: live ranks {live}/{n}  mean local loss "
              f"{float(np.asarray(loss).mean()):.4f}")
    resid = float(jnp.mean(jnp.abs(params[0] - true_w)))
    print("final |W - true|:", round(resid, 4))
    assert resid < 0.2


if __name__ == "__main__":
    main()
