"""Upstream-shaped tf.keras training script (mirrors
``examples/tensorflow2/tensorflow2_keras_mnist.py`` in the reference): the
intended diff for a migrating user is the import — ``import
horovod.tensorflow.keras as hvd`` becomes ``import
horovod_tpu.tensorflow.keras as hvd``. Synthetic MNIST-shaped data.

Run:  python examples/tensorflow2_keras_mnist.py --epochs 3
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.001)
    args = ap.parse_args()

    import tensorflow as tf

    import horovod_tpu.tensorflow.keras as hvd

    # --- the upstream script body, unchanged in structure ------------------
    hvd.init()
    tf.keras.utils.set_random_seed(42)   # deterministic weight init

    rng = np.random.default_rng(0)
    n = args.batch * 4 * hvd.size()      # 4 steps/epoch per worker
    images = rng.standard_normal((n, 28, 28, 1)).astype(np.float32)
    labels = rng.integers(0, 10, (n,)).astype(np.int64)
    # Upstream shards with dataset.shard(hvd.size(), hvd.rank()).
    dataset = (tf.data.Dataset.from_tensor_slices((images, labels))
               .shard(hvd.size(), hvd.rank())
               .shuffle(1024, seed=42).batch(args.batch).repeat())

    model = tf.keras.Sequential([
        tf.keras.layers.Input((28, 28, 1)),
        tf.keras.layers.Conv2D(16, 3, activation="relu"),
        tf.keras.layers.MaxPooling2D(),
        tf.keras.layers.Flatten(),
        tf.keras.layers.Dense(64, activation="relu"),
        tf.keras.layers.Dense(10, activation="softmax"),
    ])

    # Upstream scales the LR by the number of workers and wraps the
    # optimizer; callbacks sync initial state and average metrics.
    opt = hvd.DistributedOptimizer(
        tf.keras.optimizers.Adam(args.lr * hvd.size()))
    model.compile(optimizer=opt,
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])

    callbacks = [
        hvd.BroadcastGlobalVariablesCallback(root_rank=0),
        hvd.MetricAverageCallback(),
        hvd.LearningRateWarmupCallback(initial_lr=args.lr * hvd.size(),
                                       warmup_epochs=1, verbose=0),
    ]

    steps_per_epoch = max(1, n // hvd.size() // args.batch)
    hist = model.fit(dataset, steps_per_epoch=steps_per_epoch,
                     epochs=args.epochs, callbacks=callbacks,
                     verbose=1 if hvd.rank() == 0 else 0)

    first, last = hist.history["loss"][0], hist.history["loss"][-1]
    print(f"loss {first:.4f} -> {last:.4f}")
    assert last < first, "training did not reduce the loss"
    print("OK")


if __name__ == "__main__":
    main()
