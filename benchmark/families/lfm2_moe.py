"""LFM2 (``model_type: lfm2_moe``): from a configuration file to the
program's hybrid decoder and to the reference. The one place that knows both
the file's keys (the publisher's names, and ``deployment`` / ``assumed`` for
the chip's share and what the publisher does not give) and the program's
(``LFM2Config``).

Two things beside the weights are the same in every run: the weights stand
in for the one checkpoint that is being trained on, and the routers'
selection bias is that checkpoint's buffer (``expert_bias``: the program
takes it as an input and nothing moves it). ``--seed`` draws the tokens.

The check also judges the routing, by ``families/sdar_moe.routing_faults``
and for its reasons (the loss and the gradient norm of 32,768 positions
cannot tell a bfloat16 router from a float32 one, nor see one dropped
assignment, nor a router that forgot its bias): after the reference's loss
and gradient the family runs the program's forward once more on the check
batch and the initial weights with its auxiliary outputs kept, and holds it
to what the configuration's ``check`` block states: every choice that fell on
a held expert has its row in the grouped products (counted again, exactly);
of a routed layer's choices no more than a stated share are ones that a
float32 router **with the same rule and bias** would not make on the
program's own router inputs; no more than a stated share differ from the
reference's. Outside any, the reference's loss comes back as NaN and the
driver's check fails. ``controls_lfm2.py`` puts a dropped row, bfloat16
logits and a router that selects without the bias through the same
comparison. The same forward sets the gauges ``moe_local_assignments`` /
``moe_load_max_over_mean`` and ``moe_bias_moved_share`` (the share of the
program's choices that the top of the unbiased scores would not have made).
After the window, never in it.
"""

import dataclasses
import types

import numpy as np

import flops_lfm2
from families.gpt2 import key
from families.sdar_moe import _differ, routing_faults
from reference import lfm2_moe_ref
# a program without the family's model ends here, before the chip is asked
from horovod_tpu.models.lfm2 import LFM2, LFM2Config, loss_fn

PROGRAM = "train_step"      # the name hvd.spmd gives the driver's step
# The weights and the bias are the same in every run and ``--seed`` draws
# the tokens: a dropless share's step time follows its routers
# (families/sdar_moe.py, WEIGHTS_SEED; PERF.md, Findings PR 27 and 31), and
# the configuration file says so.
WEIGHTS_SEED = 31
# The bias is N(0, BIAS_STD) an expert and layer, from WEIGHTS_SEED
# (``assumed.expert_bias`` in the configuration says why this size).
BIAS_STD = 0.02


def program_config(config, **overrides):
    import jax.numpy as jnp
    run, assumed, deployment = (config["run"], config["assumed"],
                                config["deployment"])
    if assumed["expert_bias_std"] != BIAS_STD:
        raise ValueError("assumed.expert_bias_std and the family's BIAS_STD "
                         "have come apart")
    return LFM2Config(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        conv_taps=config["conv_L_cache"],
        experts_total=deployment["router_width"],
        experts_held=(deployment["experts_first"], config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        use_expert_bias=config["use_expert_bias"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rms_eps=config["norm_eps"], embed_std=assumed["embedding_std"],
        dtype=jnp.dtype(run["compute_dtype"]),
        attention=run.get("attention", "dense"),
        remat=run.get("remat", False),
        remat_policy=run.get("remat_policy", "full"), **overrides)


def model(cfg):
    return LFM2(cfg)


def expert_bias(cfg):
    """The routers' selection bias, (layers, router width) float32: the same
    in every run, as the weights are."""
    rng = np.random.default_rng(WEIGHTS_SEED)
    return (BIAS_STD * rng.standard_normal(
        (cfg.num_layers, cfg.experts_total))).astype(np.float32)


def loss(mdl, params, tokens):
    return loss_fn(mdl, params, tokens, expert_bias(mdl.cfg))


def make_params(cfg, seed, dtype, sharding=None):
    """Seeded random weights in ``dtype``, made on the device in one jitted
    call, through the dense, un-remat twin on a short row: the parameter
    tree is the same and no kernel is compiled to trace shapes. From
    ``WEIGHTS_SEED`` in every run and not from the run's ``seed``, which
    draws this cell's data: see the constant, and the line this prints."""
    import jax
    import jax.numpy as jnp
    twin = LFM2(dataclasses.replace(cfg, attention="dense", remat=False))

    def init_params(k):
        tree = twin.init(k, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    print(f"[lfm2_moe] weights and selection bias from the fixed seed "
          f"{WEIGHTS_SEED} (configuration, assumed.weights); --seed {seed} "
          f"draws the tokens", flush=True)
    return jax.jit(init_params, out_shardings=sharding)(key(WEIGHTS_SEED))


def reference_tree(config, params):
    return lfm2_moe_ref.from_system(params, config["num_hidden_layers"])


def system_tree(ref):
    """``from_system`` undone: the reference's tree as the program's (the
    same arrays: nothing is copied)."""
    tree = {"wte": ref["wte"], "norm_f": {"scale": ref["norm_f"]}}
    tree.update({f"h{i}": block for i, block in enumerate(ref["h"])})
    return tree


def reference_kwargs(config):
    """What the driver hands on to ``reference.loss_and_grad_norm``: the
    configuration itself, because the look at the routing needs all of it
    (:func:`shapes` is what the reference takes of it)."""
    return {"config": config}


def shapes(config):
    return {"layer_types": tuple(config["layer_types"]),
            "num_dense_layers": config["num_dense_layers"],
            "num_heads": config["num_attention_heads"],
            "num_kv_heads": config["num_key_value_heads"],
            "eps": config["norm_eps"],
            "rope_theta": float(config["rope_parameters"]["rope_theta"]),
            "top_k": config["num_experts_per_tok"],
            "norm_topk": config["norm_topk_prob"],
            "routed_scale": float(config["routed_scaling_factor"]),
            "experts_first": config["deployment"]["experts_first"]}


def routed_layers(cfg):
    return list(range(cfg.num_dense_layers, cfg.num_layers))


def routing_of(cfg, params, tokens):
    """The program's forward on ``tokens`` with its auxiliary outputs kept:
    ``(group_sizes (Lr, held), choice (Lr, B, T, top_k), inputs (Lr, B, T,
    d))`` of every routed layer: the rows each held expert was given, the
    experts every position chose, and what the router chose them from (the
    output of the block's second norm, in the compute dtype)."""
    import jax
    import jax.numpy as jnp
    mdl = LFM2(dataclasses.replace(cfg, remat=False))
    bias = expert_bias(cfg)

    @jax.jit
    def look(params, tokens):
        _, kept = mdl.apply(
            {"params": params}, tokens, bias, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "norm_ff")
        layers = [kept["intermediates"][f"h{i}"] for i in routed_layers(cfg)]
        return (jnp.stack([h["moe"]["group_sizes"][0] for h in layers]),
                jnp.stack([h["moe"]["choice"][0].reshape(
                    *tokens.shape, cfg.top_k) for h in layers]),
                jnp.stack([h["norm_ff"]["__call__"][0] for h in layers]))

    return look(params, tokens)


def routers_of(ref, cfg):
    """``(routers (Lr, d, experts), biases (Lr, experts))`` of the routed
    layers, for ``lfm2_moe_ref.router_choices``."""
    import jax.numpy as jnp
    layers = routed_layers(cfg)
    return (jnp.stack([ref["h"][i]["moe"]["router"] for i in layers]),
            jnp.asarray(expert_bias(cfg)[layers]))


def _checked(ref, tokens, *, micro, config):
    """The reference's loss and gradient norm, or NaN in their place where
    the routing of the same batch is outside the configuration's limits."""
    from horovod_tpu import tracing
    kwargs, cfg = shapes(config), program_config(config)
    bias = expert_bias(cfg)
    want = lfm2_moe_ref.loss_and_grad_norm(ref, tokens, bias, micro=micro,
                                           **kwargs)
    sizes, mine, inputs = routing_of(cfg, system_tree(ref), tokens)
    tracing.routing_load(PROGRAM, sizes)
    routers, biases = routers_of(ref, cfg)
    again = lfm2_moe_ref.router_choices(inputs, routers, biases,
                                        top_k=kwargs["top_k"])
    plain = lfm2_moe_ref.router_choices(inputs, routers, None,
                                        top_k=kwargs["top_k"])
    del inputs
    theirs = np.concatenate([
        np.asarray(lfm2_moe_ref.choices(ref, tokens[i:i + micro], bias,
                                        **kwargs))
        for i in range(0, tokens.shape[0], micro)], axis=1)
    sizes, mine = np.asarray(sizes), np.asarray(mine)
    moved = _differ(mine, np.asarray(plain))
    tracing.routing_bias_moved(PROGRAM, moved, mine[0].size)
    faults, router, differ = routing_faults(
        mine, theirs, again, sizes, kwargs["experts_first"], config["check"])
    print(f"[lfm2_moe] routing of the check batch: rows the held experts "
          f"were given, by routed layer {sizes.sum(1).tolist()} (busiest "
          f"expert over the mean {sizes.max() / sizes.mean():.3f}), each "
          f"counted again from the choices; of {mine[0].size} choices a "
          f"layer, those a float32 router with the same bias does not make "
          f"on the same inputs, by layer {router.tolist()}; those the "
          f"reference did not make, by layer {differ.tolist()}; those the "
          f"top of the unbiased scores would not have made, by layer "
          f"{moved.tolist()}", flush=True)
    if faults:
        print(f"[lfm2_moe] ROUTING OUTSIDE ITS LIMITS: {'; '.join(faults)}. "
              f"The reference read loss {want[0]:.6f} grad norm "
              f"{want[1]:.6f}; NaN goes to the driver's check in their "
              f"place, which fails", flush=True)
        return float("nan"), float("nan")
    return want


reference = types.SimpleNamespace(loss_and_grad_norm=_checked)
train_flops_per_token = flops_lfm2.train_flops_per_token
