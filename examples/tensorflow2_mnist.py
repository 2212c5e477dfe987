"""Upstream-shaped TF2 training script (mirrors
``examples/tensorflow2/tensorflow2_mnist.py`` in the reference): the only
intended change for a migrating user is the import line —
``import horovod.tensorflow as hvd`` becomes
``import horovod_tpu.tensorflow as hvd``. Synthetic MNIST-shaped data (no
dataset downloads in this image).

Run:  python examples/tensorflow2_mnist.py --steps 60
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.001)
    args = ap.parse_args()

    import tensorflow as tf

    import horovod_tpu.tensorflow as hvd

    # --- the upstream script body, unchanged in structure ------------------
    hvd.init()

    rng = np.random.default_rng(hvd.rank())
    images = rng.standard_normal(
        (args.batch * 4, 28, 28, 1)).astype(np.float32)
    labels = rng.integers(0, 10, (args.batch * 4,)).astype(np.int64)
    dataset = tf.data.Dataset.from_tensor_slices((images, labels))
    dataset = dataset.repeat().shuffle(1024).batch(args.batch)

    mnist_model = tf.keras.Sequential([
        tf.keras.layers.Conv2D(32, [3, 3], activation="relu"),
        tf.keras.layers.Flatten(),
        tf.keras.layers.Dense(64, activation="relu"),
        tf.keras.layers.Dense(10, activation="softmax"),
    ])
    loss_obj = tf.losses.SparseCategoricalCrossentropy()
    # Upstream scales the LR by the number of workers and synchronizes via
    # the tape alone (wrapping the optimizer too would allreduce twice).
    opt = tf.optimizers.Adam(args.lr * hvd.size())

    @tf.function
    def training_step(images, labels, first_batch):
        with tf.GradientTape() as tape:
            tape = hvd.DistributedGradientTape(tape)
            probs = mnist_model(images, training=True)
            loss_value = loss_obj(labels, probs)
        grads = tape.gradient(loss_value, mnist_model.trainable_variables)
        opt.apply_gradients(zip(grads, mnist_model.trainable_variables))
        if first_batch:
            # Upstream broadcasts initial state after the first step so the
            # optimizer slots exist.
            hvd.broadcast_variables(mnist_model.variables, root_rank=0)
            opt_vars = opt.variables() if callable(opt.variables) \
                else opt.variables
            hvd.broadcast_variables(opt_vars, root_rank=0)
        return loss_value

    first = None
    for batch_idx, (images, labels) in enumerate(
            dataset.take(args.steps)):
        loss_value = training_step(images, labels, batch_idx == 0)
        if first is None:
            first = float(loss_value)
        if batch_idx % 10 == 0:
            print(f"step {batch_idx}: loss {float(loss_value):.4f}")
    print(f"loss {first:.4f} -> {float(loss_value):.4f}")
    assert float(loss_value) < first, "training did not reduce the loss"


if __name__ == "__main__":
    main()
