"""Upstream-shaped PyTorch training script (mirrors
``examples/pytorch/pytorch_mnist.py`` in the reference): the intended diff
for a migrating user is the import — ``import horovod.torch as hvd``
becomes ``import horovod_tpu.torch as hvd``. Synthetic MNIST-shaped data.

Run:  python examples/pytorch_mnist.py --steps 60
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
    ap.add_argument("--lr", type=float, default=0.01)
    args = ap.parse_args()

    import torch
    import torch.nn.functional as F

    import horovod_tpu.torch as hvd
    from horovod_tpu.data import DistributedSampler

    # --- the upstream script body, unchanged in structure ------------------
    hvd.init()
    torch.manual_seed(42)

    class Net(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.conv1 = torch.nn.Conv2d(1, 10, kernel_size=5)
            self.fc1 = torch.nn.Linear(10 * 12 * 12, 50)
            self.fc2 = torch.nn.Linear(50, 10)

        def forward(self, x):
            x = F.relu(F.max_pool2d(self.conv1(x), 2))
            x = x.flatten(1)
            x = F.relu(self.fc1(x))
            return F.log_softmax(self.fc2(x), dim=1)

    model = Net()

    rng = np.random.default_rng(0)
    n = args.batch * 4
    images = torch.from_numpy(
        rng.standard_normal((n, 1, 28, 28)).astype(np.float32))
    labels = torch.from_numpy(rng.integers(0, 10, (n,)).astype(np.int64))

    # Upstream partitions with torch's DistributedSampler(rank, size);
    # same wrap-pad semantics here.
    sampler = DistributedSampler(n, rank=hvd.rank(), size=hvd.size())

    optimizer = torch.optim.SGD(model.parameters(),
                                lr=args.lr * hvd.size(), momentum=0.5)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    hvd.broadcast_optimizer_state(optimizer, root_rank=0)
    optimizer = hvd.DistributedOptimizer(optimizer)

    first = None
    step = 0
    while step < args.steps:
        indices = list(iter(sampler))
        for idx in np.array_split(indices,
                                  max(1, len(indices) // args.batch)):
            data, target = images[idx], labels[idx]
            optimizer.zero_grad()
            output = model(data)
            loss = F.nll_loss(output, target)
            loss.backward()
            optimizer.step()    # allreduces grads, then inner step
            if first is None:
                first = float(loss)
            if step % 10 == 0:
                print(f"step {step}: loss {float(loss):.4f}")
            step += 1
            if step >= args.steps:
                break
        sampler.set_epoch(step)
    print(f"loss {first:.4f} -> {float(loss):.4f}")
    assert float(loss) < first, "training did not reduce the loss"


if __name__ == "__main__":
    main()
