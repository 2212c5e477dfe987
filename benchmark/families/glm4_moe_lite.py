"""GLM-4-MoE-Lite (``model_type: glm4_moe_lite``): from a configuration file
to the program's decoder (latent attention, routed experts beside a shared
one, a multi-token-prediction module) and to the reference. The one place
that knows both the file's keys (the publisher's names, and ``deployment`` /
``assumed`` for the chip's share and what the publisher does not give) and
the program's (``Glm4MoeLiteConfig``).

Two things beside the weights are the same in every run: the weights stand
in for the one checkpoint that is being trained on, and the routers'
selection bias is that checkpoint's buffer (``expert_bias``: a row a layer
and one for the module's block; the program takes it as an input and nothing
moves it). ``--seed`` draws the tokens.

The check also judges the routing, by ``families/sdar_moe.routing_faults``
and for its reasons (``families/lfm2_moe.py`` gives them), over the routed
trunk layers **and the module's block**: after the reference's loss and
gradient the family runs the program's forward once more on the check batch
and the initial weights with its auxiliary outputs kept, and holds it to
what the configuration's ``check`` block states: every choice that fell on a
held expert has its row in the grouped products (counted again, exactly); of
a routed layer's choices no more than a stated share are ones that a float32
router with the same rule and bias would not make on the program's own
router inputs; no more than a stated share differ from the reference's. The
program runs the module's block over all ``T`` positions, the reference over
the ``T - 1`` that have a next token: the row's last position, which enters
no loss, is counted in the rows and, having no counterpart, as the
program's own choice in the comparison. Outside any limit the reference's
loss comes back as NaN and the driver's check fails. ``controls_glm4.py``
puts a dropped row, bfloat16 logits, a router without its bias, a layer
without its shared expert and a loss without its second term through the
same comparison. The same forward sets the gauges ``moe_local_assignments``
/ ``moe_load_max_over_mean`` and ``moe_bias_moved_share``. After the window,
never in it.
"""

import dataclasses
import types

import numpy as np

import flops_glm4
from families.gpt2 import key
from families.sdar_moe import _differ, routing_faults
from reference import glm4_moe_lite_ref
# a program without the family's model ends here, before the chip is asked
from horovod_tpu.models.glm4_moe_lite import (Glm4MoeLite, Glm4MoeLiteConfig,
                                              loss_fn)

PROGRAM = "train_step"      # the name hvd.spmd gives the driver's step
# The weights and the bias are the same in every run and ``--seed`` draws
# the tokens: a dropless share's step time follows its routers
# (families/sdar_moe.py, WEIGHTS_SEED; PERF.md, Findings PRs 27 and 31), and
# the configuration file says so.
WEIGHTS_SEED = 33
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
    if (config["num_key_value_heads"] != config["num_attention_heads"]
            or config["tie_word_embeddings"] or config["attention_bias"]
            or config["rope_scaling"] is not None
            or config["partial_rotary_factor"] != 1
            or config["hidden_act"] != "silu"
            or config["topk_method"] != "noaux_tc"):
        raise ValueError(
            "the program's latent attention has a key/value head a query "
            "head, an untied head, no bias, plain RoPE over the whole of "
            "the rotated part, SwiGLU experts and the noaux_tc choice: the "
            "configuration asks for something else")
    fields = dict(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_dense_layers=config["first_k_dense_replace"],
        num_heads=config["num_attention_heads"],
        d_model=config["hidden_size"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        experts_total=deployment["router_width"],
        experts_held=(deployment["experts_first"],
                      config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        shared_experts=config["n_shared_experts"],
        norm_topk=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        n_group=config["n_group"], topk_group=config["topk_group"],
        mtp=config["num_nextn_predict_layers"],
        mtp_weight=assumed["mtp_loss_weight"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"], embed_std=assumed["embedding_std"],
        dtype=jnp.dtype(run["compute_dtype"]),
        attention=run.get("attention", "dense"),
        remat=run.get("remat", False),
        remat_policy=run.get("remat_policy", "full"))
    fields.update(overrides)
    return Glm4MoeLiteConfig(**fields)


def model(cfg):
    return Glm4MoeLite(cfg)


def expert_bias(cfg):
    """The routers' selection bias, (layers + the module, router width)
    float32: the same in every run, as the weights are."""
    rng = np.random.default_rng(WEIGHTS_SEED)
    return (BIAS_STD * rng.standard_normal(
        (cfg.num_layers + cfg.mtp, cfg.experts_total))).astype(np.float32)


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
    twin = Glm4MoeLite(dataclasses.replace(cfg, attention="dense",
                                           remat=False))

    def init_params(k):
        tree = twin.init(k, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    print(f"[glm4_moe_lite] weights and selection bias from the fixed seed "
          f"{WEIGHTS_SEED} (configuration, assumed.weights); --seed {seed} "
          f"draws the tokens", flush=True)
    return jax.jit(init_params, out_shardings=sharding)(key(WEIGHTS_SEED))


def reference_tree(config, params):
    return glm4_moe_lite_ref.from_system(params, config["num_hidden_layers"])


def system_tree(ref):
    """``from_system`` undone: the reference's tree as the program's (the
    same arrays: nothing is copied)."""
    tree = {k: v for k, v in ref.items() if k not in ("h", "norm_f")}
    tree["norm_f"] = {"scale": ref["norm_f"]}
    tree.update({f"h{i}": block for i, block in enumerate(ref["h"])})
    return tree


def reference_kwargs(config):
    """What the driver hands on to ``reference.loss_and_grad_norm``: the
    configuration itself, because the look at the routing needs all of it
    (:func:`shapes` is what the reference takes of it)."""
    return {"config": config}


def shapes(config):
    return {"num_dense_layers": config["first_k_dense_replace"],
            "num_heads": config["num_attention_heads"],
            "qk_nope_head_dim": config["qk_nope_head_dim"],
            "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "top_k": config["num_experts_per_tok"],
            "norm_topk": config["norm_topk_prob"],
            "routed_scale": float(config["routed_scaling_factor"]),
            "experts_first": config["deployment"]["experts_first"],
            "mtp_weight": config["assumed"]["mtp_loss_weight"]}


def routed_layers(cfg):
    """The bias's rows that a router reads: the routed trunk layers, then
    the module's block."""
    return list(range(cfg.num_dense_layers, cfg.num_layers + cfg.mtp))


def routing_of(cfg, params, tokens):
    """The program's forward on ``tokens`` with its auxiliary outputs kept:
    ``(group_sizes (Lr, held), choice (Lr, B, T, top_k), inputs (Lr, B, T,
    d))`` of every routed layer, the module's block last: the rows each held
    expert was given, the experts every position chose, and what the router
    chose them from (the output of the block's second norm, in the compute
    dtype)."""
    import jax
    import jax.numpy as jnp
    mdl = Glm4MoeLite(dataclasses.replace(cfg, remat=False))
    bias = expert_bias(cfg)

    @jax.jit
    def look(params, tokens):
        _, kept = mdl.apply(
            {"params": params}, tokens, bias, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "norm_post")
        kept = kept["intermediates"]
        layers = [kept[f"h{i}"]
                  for i in range(cfg.num_dense_layers, cfg.num_layers)]
        layers += [kept["mtp"]["block"]] * cfg.mtp
        return (jnp.stack([h["moe"]["group_sizes"][0] for h in layers]),
                jnp.stack([h["moe"]["choice"][0].reshape(
                    *tokens.shape, cfg.top_k) for h in layers]),
                jnp.stack([h["norm_post"]["__call__"][0] for h in layers]))

    return look(params, tokens)


def routers_of(ref, cfg):
    """``(routers (Lr, d, experts), biases (Lr, experts))`` of the routed
    layers, the module's block last, for ``router_choices``."""
    import jax.numpy as jnp
    blocks = ref["h"][cfg.num_dense_layers:]
    blocks = blocks + [ref["mtp"]["block"]] * cfg.mtp
    return (jnp.stack([b["moe"]["router"] for b in blocks]),
            jnp.asarray(expert_bias(cfg)[routed_layers(cfg)]))


def reference_choices(ref, tokens, mine, *, micro, **kwargs):
    """The reference's choices on ``tokens``, a micro-batch at a time, in
    the shape of the program's ``mine`` (Lr, B, T, top_k): its module runs
    over ``T - 1`` positions, so the row's last position of that layer,
    which enters no loss, is filled with ``mine``'s own and counts as no
    difference."""
    got = [glm4_moe_lite_ref.choices(ref, tokens[i:i + micro], **kwargs)
           for i in range(0, tokens.shape[0], micro)]
    layers = [np.concatenate([np.asarray(t) for t, _ in got], axis=1)]
    if got[0][1] is not None:
        module = np.concatenate([np.asarray(m) for _, m in got], axis=0)
        layers.append(np.concatenate(
            [module, np.asarray(mine)[-1][:, -1:]], axis=1)[None])
    return np.concatenate(layers, axis=0)


def _checked(ref, tokens, *, micro, config):
    """The reference's loss and gradient norm, or NaN in their place where
    the routing of the same batch is outside the configuration's limits."""
    from horovod_tpu import tracing
    kwargs, cfg = shapes(config), program_config(config)
    bias = expert_bias(cfg)
    want = glm4_moe_lite_ref.loss_and_grad_norm(ref, tokens, bias,
                                                micro=micro, **kwargs)
    sizes, mine, inputs = routing_of(cfg, system_tree(ref), tokens)
    tracing.routing_load(PROGRAM, sizes)
    routers, biases = routers_of(ref, cfg)
    again = glm4_moe_lite_ref.router_choices(inputs, routers, biases,
                                             top_k=kwargs["top_k"])
    plain = glm4_moe_lite_ref.router_choices(inputs, routers, None,
                                             top_k=kwargs["top_k"])
    del inputs
    sizes, mine = np.asarray(sizes), np.asarray(mine)
    theirs = reference_choices(ref, tokens, mine, micro=micro,
                               expert_bias=bias, **kwargs)
    moved = _differ(mine, np.asarray(plain))
    tracing.routing_bias_moved(PROGRAM, moved, mine[0].size)
    faults, router, differ = routing_faults(
        mine, theirs, again, sizes, kwargs["experts_first"], config["check"])
    print(f"[glm4_moe_lite] routing of the check batch (the routed trunk "
          f"layers, then the module's block): rows the held experts were "
          f"given, by layer {sizes.sum(1).tolist()} (busiest expert over "
          f"the mean {sizes.max() / sizes.mean():.3f}), each counted again "
          f"from the choices; of {mine[0].size} choices a layer, those a "
          f"float32 router with the same bias does not make on the same "
          f"inputs, by layer {router.tolist()}; those the reference did not "
          f"make, by layer {differ.tolist()}; those the top of the unbiased "
          f"scores would not have made, by layer {moved.tolist()}",
          flush=True)
    if faults:
        print(f"[glm4_moe_lite] ROUTING OUTSIDE ITS LIMITS: "
              f"{'; '.join(faults)}. The reference read loss {want[0]:.6f} "
              f"grad norm {want[1]:.6f}; NaN goes to the driver's check in "
              f"their place, which fails", flush=True)
        return float("nan"), float("nan")
    return want


reference = types.SimpleNamespace(loss_and_grad_norm=_checked)
train_flops_per_token = flops_glm4.train_flops_per_token
