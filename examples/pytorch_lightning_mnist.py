"""Upstream-shaped Lightning training script (mirrors
``examples/pytorch/pytorch_lightning_mnist.py`` in the reference): the
LightningModule is standard; distribution comes from
``horovod_tpu.lightning.HorovodStrategy`` (with pytorch-lightning
installed, pass the strategy to ``pl.Trainer``; the bundled ``Trainer``
drives the same protocol without the dependency). Synthetic MNIST-shaped
data.

Run:  python examples/pytorch_lightning_mnist.py --epochs 4
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.02)
    args = ap.parse_args()

    import torch
    import torch.nn.functional as F

    from horovod_tpu.data import DistributedSampler
    from horovod_tpu.lightning import HorovodStrategy, Trainer

    # --- a standard LightningModule-shaped model ---------------------------
    class LitMnist(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.conv = torch.nn.Conv2d(1, 10, kernel_size=5)
            self.fc1 = torch.nn.Linear(10 * 12 * 12, 50)
            self.fc2 = torch.nn.Linear(50, 10)

        def forward(self, x):
            x = F.relu(F.max_pool2d(self.conv(x), 2))
            x = F.relu(self.fc1(x.flatten(1)))
            return F.log_softmax(self.fc2(x), dim=1)

        def training_step(self, batch, batch_idx):
            data, target = batch
            return F.nll_loss(self(data), target)

        def configure_optimizers(self):
            return torch.optim.SGD(self.parameters(), lr=args.lr,
                                   momentum=0.5)

    torch.manual_seed(42)
    model = LitMnist()

    rng = np.random.default_rng(0)
    n = args.batch * 4
    images = torch.from_numpy(
        rng.standard_normal((n, 1, 28, 28)).astype(np.float32))
    labels = torch.from_numpy(rng.integers(0, 10, (n,)).astype(np.int64))

    strategy = HorovodStrategy()
    sampler = DistributedSampler(n, rank=strategy.global_rank,
                                 size=strategy.world_size)
    idx = torch.as_tensor(np.asarray(list(iter(sampler))))
    loader = [(images[i], labels[i])
              for i in torch.split(idx, args.batch)]

    trainer = Trainer(max_epochs=args.epochs, strategy=strategy)
    trainer.fit(model, loader)

    first, last = trainer.history[0], trainer.history[-1]
    if strategy.is_global_zero:
        print(f"loss {first:.4f} -> {last:.4f}")
    assert last < first, "training did not reduce the loss"
    print("OK")


if __name__ == "__main__":
    main()
