"""MNIST CNN training — the framework's hello-world, mirroring the reference
example ``examples/tensorflow2/tensorflow2_keras_mnist.py`` on the JAX
frontend (synthetic data: no datasets ship in the image).

Run single-host:      python examples/mnist_train.py
Virtual 8-dev CPU:    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                      JAX_PLATFORMS=cpu python examples/mnist_train.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.callbacks import MetricAverageCallback, warmup_schedule
from horovod_tpu.models import MnistCNN


def main(epochs: int = 2, steps_per_epoch: int = 10, batch: int = 32):
    hvd.init()
    print(f"communicator: size={hvd.size()} backend={jax.default_backend()}")

    model = MnistCNN()
    rng = np.random.default_rng(42)
    x0 = jnp.zeros((batch, 28, 28, 1), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x0, train=False)
    params = variables["params"]

    # Horovod recipe: scale LR by size with warmup, then wrap the optimizer.
    sched = warmup_schedule(1e-3, warmup_epochs=1,
                            steps_per_epoch=steps_per_epoch)
    opt = hvd.DistributedOptimizer(optax.adam(sched))
    opt_state = opt.init(params)

    def train_step(params, opt_state, images, labels):
        params = hvd.broadcast_parameters(params, root_rank=0)

        def loss_fn(p):
            logits = model.apply({"params": p}, images, train=False)
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), labels[:, None], 1))

        # The one gradient sync of the step (the optimizer is handed what
        # it averaged and lowers no second pass), so the wire option is here.
        loss, grads = hvd.value_and_grad(
            loss_fn, compression=hvd.Compression.bf16)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = hvd.spmd(train_step,
                    in_specs=(P(), P(), P("hvd"), P("hvd")),
                    out_specs=(P(), P(), P()))

    metric_cb = MetricAverageCallback()
    n = hvd.size()
    for epoch in range(epochs):
        losses = []
        for _ in range(steps_per_epoch):
            images = jnp.asarray(
                rng.standard_normal((batch * n, 28, 28, 1)), jnp.float32)
            labels = jnp.asarray(rng.integers(0, 10, (batch * n,)), jnp.int32)
            params, opt_state, loss = step(params, opt_state, images, labels)
            losses.append(float(loss))
        avg = metric_cb.on_epoch_end({"loss": float(np.mean(losses))})
        print(f"epoch {epoch}: loss={float(avg['loss']):.4f}")


if __name__ == "__main__":
    main()
