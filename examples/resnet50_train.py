"""ResNet-50 data-parallel training (the headline benchmark config;
reference ``examples/pytorch/pytorch_imagenet_resnet50.py``), with
checkpointing, timeline, and the health watchdog — synthetic ImageNet shapes.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import timeline as tl
from horovod_tpu.callbacks import warmup_schedule
from horovod_tpu.models import ResNet50
from horovod_tpu.utils import HealthWatchdog


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-per-chip", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--timeline", default=None)
    args = ap.parse_args()

    hvd.init()
    n = hvd.size()
    if args.timeline:
        tl.init_timeline(args.timeline)

    model = ResNet50(num_classes=1000)
    B = args.batch_per_chip * n
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal(
        (B, args.image_size, args.image_size, 3)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (B,)), jnp.int32)

    variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    sched = warmup_schedule(0.1, warmup_epochs=5, steps_per_epoch=args.steps)
    opt = hvd.DistributedOptimizer(optax.sgd(sched, momentum=0.9))
    opt_state = opt.init(params)

    def train_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p, bs):
            logits, upd = model.apply(
                {"params": p, "batch_stats": bs}, images, train=True,
                mutable=["batch_stats"])
            loss = -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), labels[:, None], 1))
            return loss, upd["batch_stats"]

        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats)
        # The one gradient sync of the step (the optimizer is handed what
        # it averaged and lowers no second pass), so the wire option is here.
        grads = hvd.allreduce_gradients(
            grads, compression=hvd.Compression.bf16)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), batch_stats, \
            opt_state, loss

    step = hvd.spmd(train_step,
                    in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
                    out_specs=(P(), P(), P(), P()),
                    donate_argnums=(0, 1, 2))

    with HealthWatchdog(timeout_s=300):
        t0 = time.perf_counter()
        for i in range(args.steps):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, images, labels)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
    print(f"{B * args.steps / dt:.1f} images/sec total "
          f"({B * args.steps / dt / n:.1f}/chip), final loss "
          f"{float(loss):.3f}")

    if args.checkpoint_dir:
        from horovod_tpu.checkpoint import save_checkpoint
        save_checkpoint(args.checkpoint_dir,
                        {"params": params, "batch_stats": batch_stats},
                        step=args.steps)
        print(f"checkpoint saved to {args.checkpoint_dir}")
    if args.timeline:
        tl.shutdown_timeline()


if __name__ == "__main__":
    main()
