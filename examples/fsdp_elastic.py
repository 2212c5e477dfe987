"""Elastic FSDP/ZeRO-3 training: a flat-shard state surviving a re-mesh
(upstream analogue: ``horovod/common/elastic.py`` state semantics over
DeepSpeed-ZeRO-on-hvd).

The ZeRO-3 state is world-size-DEPENDENT — each device owns a ``(c,)``
chunk of the padded flat parameter/optimizer vectors with
``c = ceil(len/n)`` — so an elastic resume cannot replay raw snapshots
the way ``JaxState`` does. :class:`~horovod_tpu.elastic.FsdpState`
commits a canonical (padding-stripped) form and re-pads for whatever
communicator exists after recovery; the flat AdamW math is elementwise,
so training continues numerically as if the mesh never changed.

Preemption is simulated on the virtual mesh (half the devices drop after
a few steps) so the recovery path actually executes:

  JAX_PLATFORMS=cpu python examples/fsdp_elastic.py
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
from horovod_tpu.elastic import FsdpState, HostsUpdatedInterrupt, run
from horovod_tpu.elastic.discovery import DeviceDiscovery
from horovod_tpu.parallel.fsdp import (fsdp_adamw, fsdp_apply,
                                       fsdp_shard_params)

TOTAL_STEPS = 10
PREEMPT_AT = 5
D = 16


def _mlp_template():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {
        "w1": jax.random.normal(k1, (D, 2 * D), jnp.float32) * 0.3,
        "b1": jnp.zeros((2 * D,), jnp.float32),
        "w2": jax.random.normal(k2, (2 * D, D), jnp.float32) * 0.3,
        "b2": jnp.zeros((D,), jnp.float32),
    }


def _block(p, x):
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    return x + h @ p["w2"] + p["b2"]


def main():
    hvd.init()
    all_devs = jax.devices()
    current = {"devs": all_devs}
    disco = DeviceDiscovery(probe=lambda: current["devs"])

    template = _mlp_template()
    tx = fsdp_adamw(0.05)
    shard = fsdp_shard_params(template)
    state = FsdpState(template, shard=shard, opt_state=tx.init(shard),
                      step=0)
    rng = np.random.default_rng(0)

    def make_step():
        def body(shard, opt_state, xs):
            def loss(s):
                return jnp.mean(fsdp_apply(_block, template, s, xs) ** 2)

            l, g = jax.value_and_grad(loss)(shard)
            upd, opt_state = tx.update(g, opt_state, shard)
            # The gradient is already the dp mean (fsdp's psum_scatter);
            # the reported loss needs its own pmean to be the GLOBAL
            # batch mean rather than one device's slice.
            return (optax.apply_updates(shard, upd), opt_state,
                    jax.lax.pmean(l, "hvd"))

        return hvd.spmd(body, in_specs=(P("hvd"), P("hvd"), P("hvd")),
                        out_specs=(P("hvd"), P("hvd"), P()))

    @run
    def train(state):
        step_fn = make_step()        # retraces against the current mesh
        n = hvd.size()
        c = state.shard.shape[0] // n
        print(f"[world {n}: {c} params/device of "
              f"{state.shard.shape[0]} padded]")
        while state.step < TOTAL_STEPS:
            if (state.step == PREEMPT_AT
                    and len(current["devs"]) == len(all_devs)
                    and len(all_devs) > 1):
                current["devs"] = all_devs[:max(1, len(all_devs) // 2)]
                print(f"[simulated preemption at step {state.step}]")
                raise HostsUpdatedInterrupt("preempted")
            # Fixed global batch regardless of world size: per-device
            # means over equal slices combine to the same global mean.
            X = jnp.asarray(rng.standard_normal((8, D)), jnp.float32)
            state.shard, state.opt_state, loss = step_fn(
                state.shard, state.opt_state, X)
            state.step += 1
            state.commit()
            print(f"step {state.step} on {n} devices: "
                  f"loss={float(loss):.5f}")

    train(state, discovery=disco)
    print(f"done: {state.step} steps, final communicator size "
          f"{hvd.size()}, shard re-padded to {state.shard.shape[0]}")
    assert state.step == TOTAL_STEPS


if __name__ == "__main__":
    main()
