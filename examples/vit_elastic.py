"""ViT elastic training (reference config "ViT-B/16 elastic training,
preemptible v5e"): JaxState commit/restore + hvd.elastic.run around the
train loop. Preemption is simulated on the virtual mesh (drop half the
devices after a few steps) so the recovery path actually executes.
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
from horovod_tpu.elastic import JaxState, run, HostsUpdatedInterrupt
from horovod_tpu.elastic.discovery import DeviceDiscovery
from horovod_tpu.models.vit import ViT, ViTConfig

TOTAL_STEPS = 10
PREEMPT_AT = 5


def main():
    hvd.init()
    all_devs = jax.devices()
    current = {"devs": all_devs}
    disco = DeviceDiscovery(probe=lambda: current["devs"])

    cfg = ViTConfig.tiny()
    model = ViT(cfg)
    rng = np.random.default_rng(0)
    x0 = jnp.zeros((2, cfg.image_size, cfg.image_size, 3))
    params = model.init(jax.random.PRNGKey(0), x0)["params"]
    opt = optax.adam(1e-3)
    state = JaxState(params=params, opt_state=opt.init(params), step=0)

    def make_step():
        def train_step(params, opt_state, images, labels):
            def loss_fn(p):
                logits = model.apply({"params": p}, images)
                return -jnp.mean(jnp.take_along_axis(
                    jax.nn.log_softmax(logits), labels[:, None], 1))

            loss, grads = hvd.value_and_grad(loss_fn)(params)
            updates, opt_state2 = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state2, loss

        return hvd.spmd(train_step,
                        in_specs=(P(), P(), P("hvd"), P("hvd")),
                        out_specs=(P(), P(), P()))

    @run
    def train(state):
        step_fn = make_step()  # retraces against the current mesh
        n = hvd.size()
        while state.step < TOTAL_STEPS:
            if state.step == PREEMPT_AT and len(current["devs"]) == len(all_devs) \
                    and len(all_devs) > 1:
                current["devs"] = all_devs[:max(1, len(all_devs) // 2)]
                print(f"[simulated preemption at step {state.step}]")
                raise HostsUpdatedInterrupt("preempted")
            images = jnp.asarray(rng.standard_normal(
                (2 * n, cfg.image_size, cfg.image_size, 3)), jnp.float32)
            labels = jnp.asarray(rng.integers(0, cfg.num_classes, (2 * n,)),
                                 jnp.int32)
            state.params, state.opt_state, loss = step_fn(
                state.params, state.opt_state, images, labels)
            state.step += 1
            state.commit()
            print(f"step {state.step} on {n} devices: loss={float(loss):.4f}")

    train(state, discovery=disco)
    print(f"done: {state.step} steps, final communicator size {hvd.size()}")


if __name__ == "__main__":
    main()
