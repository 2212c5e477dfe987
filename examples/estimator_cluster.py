"""Spark/Ray-style orchestration: train a model through the estimator
fit/transform state machine and run functions on a worker fleet via the
RayExecutor — both against the injected cluster backend (local processes
here; a ray/Spark cluster binds the same contract when those packages
exist).

Run:
    python examples/estimator_cluster.py --workers 2
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--epochs", type=int, default=10)
    args = ap.parse_args()

    import flax.linen as nn
    import jax.numpy as jnp

    from horovod_tpu.cluster import LocalProcessBackend
    from horovod_tpu.ray import RayExecutor
    from horovod_tpu.spark import JaxEstimator

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.relu(nn.Dense(16)(x))
            return nn.Dense(1)(h)[..., 0]

    def mse(pred, label):
        return jnp.mean((pred - label) ** 2)

    rng = np.random.default_rng(0)
    X = rng.standard_normal((256, 4)).astype(np.float32)
    y = (np.sin(X[:, 0]) + 0.5 * X[:, 1]).astype(np.float32)

    # --- Estimator: fit on partitioned data, transform on the driver ------
    est = JaxEstimator(MLP(), mse, lr=5e-3, epochs=args.epochs,
                       batch_size=32,
                       backend=LocalProcessBackend(args.workers))
    model = est.fit({"features": X, "label": y})
    hist = est.last_fit_results[0]["history"]
    print(f"estimator: {args.workers} workers, loss {hist[0]:.4f} -> "
          f"{hist[-1]:.4f}")
    out = model.transform({"features": X, "label": y})
    print("transform residual:",
          float(np.abs(out["prediction"] - y).mean()))

    # --- RayExecutor: run a function on every rendezvoused worker ---------
    ex = RayExecutor(backend=LocalProcessBackend(args.workers,
                                                 coordinator_port=29960))
    ex.start()

    def report():
        import jax

        import horovod_tpu as hvd
        return {"rank": jax.process_index(), "world": jax.process_count(),
                "backend": jax.default_backend(),
                "build": hvd.build_info()["backend"]}

    for r in ex.run(report):
        print("worker:", r)
    ex.shutdown()


if __name__ == "__main__":
    main()
