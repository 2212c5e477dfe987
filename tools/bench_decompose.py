"""Decompose the ResNet-50 step: fwd-only vs fwd+bwd, BN vs GroupNorm vs
no-norm, first-conv variants, batch sizes. Identifies the bottleneck on the
real chip."""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax


def timeit(fn, *args, steps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps * 1e3  # ms


def flops_of(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(c, list):
        c = c[0]
    return c.get("flops", 0.0)


def main():
    from horovod_tpu.models import ResNet50
    from horovod_tpu.models.resnet import ResNet, BottleneckBlock

    batch = 128
    images = jnp.asarray(
        np.random.default_rng(0).standard_normal((batch, 224, 224, 3)),
        jnp.bfloat16)
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, 1000, (batch,)), jnp.int32)

    model = ResNet50(num_classes=1000)
    variables = model.init(jax.random.PRNGKey(0), images, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    # 1. fwd only (train mode, mutable stats)
    @jax.jit
    def fwd(params, batch_stats, images):
        logits, upd = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        return logits, upd

    ms = timeit(fwd, params, batch_stats, images)
    fl = flops_of(lambda p, b, i: fwd(p, b, i), params, batch_stats, images)
    print(f"fwd-only(train):   {ms:7.2f} ms  {fl/1e9:8.1f} GFLOP  "
          f"{fl/ms*1e3/1e12:6.1f} TF/s", flush=True)

    # 2. fwd eval mode (no stats update)
    @jax.jit
    def fwd_eval(params, batch_stats, images):
        return model.apply({"params": params, "batch_stats": batch_stats},
                           images, train=False)

    ms = timeit(fwd_eval, params, batch_stats, images)
    fl = flops_of(lambda p, b, i: fwd_eval(p, b, i), params, batch_stats,
                  images)
    print(f"fwd-only(eval):    {ms:7.2f} ms  {fl/1e9:8.1f} GFLOP  "
          f"{fl/ms*1e3/1e12:6.1f} TF/s", flush=True)

    # 3. full train step (grads only, no optimizer)
    def loss_fn(params, batch_stats, images, labels):
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
        return loss, updates["batch_stats"]

    @jax.jit
    def grad_step(params, batch_stats, images, labels):
        (l, bs), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch_stats, images, labels)
        return l, bs, g

    ms = timeit(grad_step, params, batch_stats, images, labels)
    fl = flops_of(lambda p, b, i, y: grad_step(p, b, i, y), params,
                  batch_stats, images, labels)
    print(f"fwd+bwd:           {ms:7.2f} ms  {fl/1e9:8.1f} GFLOP  "
          f"{fl/ms*1e3/1e12:6.1f} TF/s", flush=True)

    # 4. batch sweep on full step, finer granularity
    for b in (64, 96, 160, 192, 256):
        im = jnp.asarray(
            np.random.default_rng(0).standard_normal((b, 224, 224, 3)),
            jnp.bfloat16)
        lb = jnp.asarray(
            np.random.default_rng(1).integers(0, 1000, (b,)), jnp.int32)
        ms = timeit(grad_step, params, batch_stats, im, lb)
        print(f"fwd+bwd b={b:3d}:    {ms:7.2f} ms  "
              f"img/s={b/ms*1e3:7.1f}", flush=True)


if __name__ == "__main__":
    main()
