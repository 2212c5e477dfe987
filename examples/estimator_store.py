"""Durable-store estimator flow (upstream ``horovod.spark`` + its
``common/store.py`` / petastorm data path): materialise a dataset into a
Store once, train with workers streaming ONLY their shard partition, and
reload the trained weights from the store's checkpoint directory — no
DataFrame or driver arrays anywhere near the workers after staging.

Run:
    python examples/estimator_store.py --workers 2 [--store /tmp/hvd_store]
"""

import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--store", default=None,
                    help="store path or fsspec URL (default: a temp dir)")
    args = ap.parse_args()

    import contextlib

    from horovod_tpu.data.store import Store

    # ExitStack: the temp store is removed even when training or an
    # assertion below fails.
    with contextlib.ExitStack() as stack:
        if args.store is None:
            args.store = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="hvd_store_"))
        _run_demo(args, Store.create(args.store))


def _run_demo(args, store):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.cluster import LocalProcessBackend
    from horovod_tpu.data.store import read_meta
    from horovod_tpu.spark import JaxEstimator, load_checkpoint

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.relu(nn.Dense(16)(x))
            return nn.Dense(1)(h)[..., 0]

    def mse(pred, label):
        return jnp.mean((pred - label) ** 2)

    rng = np.random.default_rng(0)
    X = rng.standard_normal((256, 4)).astype(np.float32)
    y = (X @ np.array([1.0, -2.0, 0.5, 0.8], np.float32)).astype(np.float32)

    est = JaxEstimator(
        MLP(), mse, lr=0.05, epochs=args.epochs, batch_size=16,
        store=store, run_id="demo", num_shards=2 * args.workers,
        validation=0.125,           # held out, materialised separately
        backend=LocalProcessBackend(args.workers, coordinator_port=29820))

    model = est.fit({"features": X, "label": y})

    meta = read_meta(store, store.train_data_path("demo"))
    print(f"staged {meta['total_rows']} rows as {len(meta['shards'])} "
          f"{meta['format']} shards under {store.prefix} "
          f"(+ {read_meta(store, store.val_data_path('demo'))['total_rows']}"
          f" val rows)")
    for r in est.last_fit_results:
        print(f"  rank {r['rank']}: read only {r['files_read']}, "
              f"loss {r['history'][0]:.3f} -> {r['history'][-1]:.3f}")
    hist = model.get_history()
    print(f"val loss per epoch: "
          f"{[round(v, 3) for v in hist['val_loss']]}")
    assert hist["val_loss"][-1] < hist["val_loss"][0]

    # The same composed pipeline the workers trained through, user-side:
    # background shard reads + in-flight device_puts (data/prefetch.py).
    from horovod_tpu.data.store import ShardedDatasetReader
    reader = ShardedDatasetReader(store, store.train_data_path("demo"))
    with reader.prefetched_batches(16, shuffle=False) as batches:
        dev_losses = [float(mse(model.predict(b["features"]), b["label"]))
                      for b in batches]
    print(f"store-side eval over {len(dev_losses)} prefetched "
          f"device batches: {np.mean(dev_losses):.4f}")
    reads = [set(r["files_read"]) for r in est.last_fit_results]
    assert set.union(*reads) == {s["file"] for s in meta["shards"]}
    assert not set.intersection(*reads), "partitions must be disjoint"

    # The trained weights are durable too: reload them store-side.
    ckpt = load_checkpoint(store, "demo")
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        ckpt["params"], model.params)
    pred = model.predict(X[:4])
    print(f"reloaded checkpoint matches; predictions {np.round(pred, 2)}")


if __name__ == "__main__":
    main()
