"""GPT-2 medium throughput sweep: batch size x remat policy x attention."""
import os, sys, time, dataclasses
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from functools import partial
import jax, jax.numpy as jnp, numpy as np, optax

def sync(x):
    np.asarray(jax.device_get(jax.tree_util.tree_leaves(x)[0])).ravel()[:1]

def run_one(B, T, remat, attention, policy="full", steps=8):
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn
    cfg = dataclasses.replace(GPT2Config.medium(), attention=attention,
                              remat=remat, remat_policy=policy)
    model = GPT2(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    tx = optax.adamw(1e-4)
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        _, g = jax.value_and_grad(
            lambda p: loss_fn(model.apply({"params": p}, tokens), tokens))(params)
        u, opt_state = tx.update(g, opt_state, params)
        return optax.apply_updates(params, u), opt_state

    tag = f"B={B:3d} T={T} remat={int(remat)}/{policy:4s} {attention:6s}"
    try:
        c = step.lower(params, opt_state).compile().cost_analysis()
        if isinstance(c, list): c = c[0]
        fl = float(c.get("flops", 0.0))
        state = (params, opt_state)
        state = step(*state); state = step(*state); sync(state)
        t0 = time.perf_counter()
        for _ in range(steps):
            state = step(*state)
        sync(state)
        dt = (time.perf_counter() - t0) / steps
        line = (f"{tag} step={dt*1e3:8.1f}ms tok/s={B*T/dt:9.0f} "
                f"TF/s={fl/dt/1e12:6.1f} MFU={fl/dt/1e12/197*100:5.1f}%")
    except Exception as e:
        line = f"{tag}: FAILED {type(e).__name__}: {str(e)[:120]}"
    print(line, flush=True)
    # every finished config is durable if the sweep dies midway
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "SWEEP_GPT2.txt"), "a") as f:
        f.write(line + "\n")

if __name__ == "__main__":
    # priority order: the configs most likely to move MFU come first, so
    # a sweep cut short still answers the main questions.
    for B, remat, att, pol in [
            (8, True, "flash", "dots"),    # selective remat at bench config
            (8, True, "flash", "full"),    # tuned-tile reference point
            (16, True, "flash", "dots"),
            (16, True, "flash", "full"),
            (8, False, "flash", "full"),   # no remat at all
            (32, True, "flash", "dots"),
            (16, True, "dense", "full"),   # flash vs XLA-fused dense
            (32, False, "flash", "full")]:
        run_one(B, 1024, remat, att, pol)
