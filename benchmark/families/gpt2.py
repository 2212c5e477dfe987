"""GPT-2: from a configuration file to the program's model and to the
reference. A family is the one place that knows both the file's keys (the
publisher's names) and the program's (``GPT2Config``)."""

import dataclasses

import flops
from reference import gpt2_ref


def program_config(config, **overrides):
    import jax.numpy as jnp
    from horovod_tpu.models.gpt2 import GPT2Config
    run = config["run"]
    return GPT2Config(
        vocab_size=config["assumed"]["vocab_padded"],
        max_seq_len=config["n_positions"], num_layers=config["n_layer"],
        num_heads=config["n_head"], d_model=config["n_embd"],
        ln_eps=config["layer_norm_epsilon"],
        dtype=jnp.dtype(run["compute_dtype"]),
        attention=run.get("attention", "dense"),
        remat=run.get("remat", False),
        remat_policy=run.get("remat_policy", "full"), **overrides)


def model(cfg):
    from horovod_tpu.models.gpt2 import GPT2
    return GPT2(cfg)


def loss(mdl, params, tokens):
    from horovod_tpu.models.gpt2 import loss_fn
    return loss_fn(mdl.apply({"params": params}, tokens), tokens)


def key(seed):
    """A PRNG key from any non-negative ``--seed``, 2**31 and above too."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_params(cfg, seed, dtype, sharding=None):
    """Seeded random weights in ``dtype``, made on the device in one jitted
    call. Initialised through the dense, un-remat twin on a short row: the
    parameter tree is the same and no kernel is compiled to trace shapes."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.gpt2 import GPT2
    twin = GPT2(dataclasses.replace(cfg, attention="dense", remat=False))

    def init_params(k):
        tree = twin.init(k, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    return jax.jit(init_params, out_shardings=sharding)(key(seed))


def reference_tree(config, params):
    return gpt2_ref.from_system(params, config["n_layer"])


def reference_kwargs(config):
    return {"num_heads": config["n_head"],
            "eps": config["layer_norm_epsilon"]}


reference = gpt2_ref
train_flops_per_token = flops.gpt2_train_flops_per_token
