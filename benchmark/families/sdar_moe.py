"""SDAR (``model_type: sdar_moe``): from a configuration file to the
program's block-diffusion decoder and to the reference. The one place that
knows both the file's keys (the publisher's names, and ``deployment`` /
``assumed`` for the chip's share and the training convention) and the
program's (``SDARConfig``).

The noise is data: a row's levels and mask follow from a key folded from
the row's own tokens (``reference.sdar_moe_ref.row_keys``), so the timed
step, the check step and the reference's micro-batches see the same noise
and the driver hands over nothing but tokens. The system draws it with the
program's own sampler, the reference with its own.

The check also judges the routing (``_checked``, ``routing_faults``). The
loss and the gradient norm of 16,384 positions cannot tell a bfloat16 router
from a float32 one, nor see one dropped assignment (PERF.md, Findings PR
27), so after the reference's loss and gradient the family runs the
program's forward once more on the check batch and the initial weights with
its auxiliary outputs kept and holds it to three things the configuration's
``check`` block states: every choice that fell on a held expert has its row
in the grouped products (counted again here, exactly); of a layer's choices
no more than a stated share are ones a float32 router would not make on the
program's own router inputs (the router's precision and nothing else); and
no more than a stated share differ from the reference's (what came before
the router). Outside any, the reference's loss comes back as NaN and the
driver's check fails. ``controls_sdar.py`` puts the nearest
precision below through the same comparison. The same forward sets the
gauges ``moe_local_assignments`` / ``moe_load_max_over_mean``. After the
window, never in it.
"""

import dataclasses
import types

import flops_sdar
from families.gpt2 import key
from reference import sdar_moe_ref

PROGRAM = "train_step"      # the name hvd.spmd gives the driver's step
# The weights stand in for the one checkpoint that is being adapted, so they
# are the same in every run, and ``--seed`` draws the tokens (and with them
# the noise). A dropless expert layer does the work its routing gives it:
# on a share of 16 experts the rows a step differ from one set of random
# routers to the next by tens of per cent, and the step's time with them.
# With weights from ``--seed`` the cell's ``train_tokens_per_s_chip`` spread
# 0.8 to 0.9 % over seeds at its best (PERF.md, Findings PR 27), against a
# bound of 1 % and half of that for a new cell to be admitted: so every
# check of this cell sees one realisation of that spread, which is the
# price, and the configuration file says so.
WEIGHTS_SEED = 27


def program_config(config, **overrides):
    import jax.numpy as jnp
    from horovod_tpu.models.sdar import SDARConfig
    run, assumed, deployment = (config["run"], config["assumed"],
                                config["deployment"])
    return SDARConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_model=config["hidden_size"],
        d_expert=config["moe_intermediate_size"],
        experts_total=deployment["router_width"],
        experts_held=(deployment["experts_first"], config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        block_len=assumed["block_length"], mask_id=assumed["mask_id"],
        t_min=assumed["t_min"], embed_std=assumed["embedding_std"],
        dtype=jnp.dtype(run["compute_dtype"]),
        attention=run.get("attention", "dense"),
        remat=run.get("remat", False),
        remat_policy=run.get("remat_policy", "full"), **overrides)


def model(cfg):
    from horovod_tpu.models.sdar import SDAR
    return SDAR(cfg)


def _noise(cfg, tokens):
    from horovod_tpu.models.sdar import block_noise
    return block_noise(sdar_moe_ref.row_keys(tokens), tokens.shape[1],
                       cfg.block_len, cfg.t_min)


def loss(mdl, params, tokens):
    from horovod_tpu.models.sdar import loss_fn
    return loss_fn(mdl, params, tokens, _noise(mdl.cfg, tokens))


def make_params(cfg, seed, dtype, sharding=None):
    """Seeded random weights in ``dtype``, made on the device in one jitted
    call, through the dense, un-remat twin on a short row: the parameter
    tree is the same and no kernel is compiled to trace shapes. From
    ``WEIGHTS_SEED`` in every run and not from the run's ``seed``, which the
    driver hands to every family and which draws this cell's data: see the
    constant, and the line this prints."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.sdar import SDAR
    twin = SDAR(dataclasses.replace(cfg, attention="dense", remat=False))

    def init_params(k):
        row = jnp.zeros((1, 2 * cfg.block_len), jnp.int32)
        tree = twin.init(k, row, row)["params"]
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    print(f"[sdar_moe] weights from the fixed seed {WEIGHTS_SEED} "
          f"(configuration, assumed.weights); --seed {seed} draws the "
          f"tokens and the noise", flush=True)
    return jax.jit(init_params, out_shardings=sharding)(key(WEIGHTS_SEED))


def reference_tree(config, params):
    return sdar_moe_ref.from_system(params, config["num_hidden_layers"])


def system_tree(ref):
    """``from_system`` undone: the reference's stacked tree as the
    program's (made when the look needs it, so that the system's weights
    are not held while the reference takes its gradients)."""
    import jax
    tree = {"wte": ref["wte"], "lm_head": ref["lm_head"],
            "norm_f": {"scale": ref["norm_f"]}}
    for i in range(ref["h"]["norm_attn"]["scale"].shape[0]):
        tree[f"h{i}"] = jax.tree_util.tree_map(lambda x: x[i], ref["h"])
    return tree


def reference_kwargs(config):
    """What the driver hands on to ``reference.loss_and_grad_norm``: the
    configuration itself, because the look at the routing needs all of it
    (:func:`shapes` is what the reference takes of it)."""
    return {"config": config}


def shapes(config):
    return {"num_heads": config["num_attention_heads"],
            "num_kv_heads": config["num_key_value_heads"],
            "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "top_k": config["num_experts_per_tok"],
            "norm_topk": config["norm_topk_prob"],
            "experts_first": config["deployment"]["experts_first"],
            "block_len": config["assumed"]["block_length"],
            "t_min": config["assumed"]["t_min"],
            "mask_id": config["assumed"]["mask_id"]}


def routing_of(cfg, params, tokens):
    """The program's forward on ``tokens`` with its auxiliary outputs kept:
    ``(group_sizes (L, held), choice (L, B, 2T, top_k), inputs (L, B, 2T,
    d))`` of every layer: the rows each held expert was given, the experts
    every position chose, and what the router chose them from (the output
    of the block's second norm, in the compute dtype)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.sdar import SDAR
    mdl = SDAR(dataclasses.replace(cfg, remat=False))

    @jax.jit
    def look(params, tokens):
        _, masked = _noise(cfg, tokens)
        noisy = jnp.where(masked, cfg.mask_token, tokens)
        _, kept = mdl.apply(
            {"params": params}, noisy, tokens, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "norm_mlp")
        layers = [kept["intermediates"][f"h{i}"]
                  for i in range(cfg.num_layers)]
        return (jnp.stack([h["moe"]["group_sizes"][0] for h in layers]),
                jnp.stack([h["moe"]["choice"][0].reshape(
                    tokens.shape[0], -1, cfg.top_k) for h in layers]),
                jnp.stack([h["norm_mlp"]["__call__"][0] for h in layers]))

    return look(params, tokens)


def _differ(mine, theirs):
    """By layer: the choices of ``mine`` (L, ..., top_k) that ``theirs``
    did not make at the same position."""
    L = mine.shape[0]
    return (mine[..., :, None] != theirs[..., None, :]).all(-1).reshape(
        L, -1).sum(axis=1)


def routing_faults(mine, theirs, again, sizes, first, limits):
    """What of one batch's routing lies outside ``limits`` (the
    configuration's ``check`` block), as a list of sentences (empty:
    sound), and the two counts by layer. ``mine`` (L, B, S, top_k) are the
    experts every position chose on the side that is judged, ``theirs`` the
    reference's choices, ``again`` the choices of a float32 router on the
    judged side's own router inputs; ``sizes`` (L, held) the rows the
    judged side's grouped products were given for the experts ``first ..
    first + held - 1``."""
    import numpy as np
    mine, theirs, again, sizes = (np.asarray(a) for a in
                                  (mine, theirs, again, sizes))
    held, faults, per_layer = sizes.shape[1], [], mine[0].size
    # nothing dropped: a held expert's rows are the choices that named it
    local = mine - first
    counted = np.stack([np.bincount(
        layer[(layer >= 0) & (layer < held)], minlength=held)
        for layer in local])
    if not np.array_equal(counted, sizes):
        lost = (counted - sizes).sum(axis=1).tolist()
        faults.append(f"choices on the held experts without a row in the "
                      f"grouped products, by layer {lost}")
    # the router's own precision: the same inputs, routed again in float32
    router = _differ(mine, again.reshape(mine.shape))
    limit = limits["router_differ_share_max"] * per_layer
    if (router > limit).any():
        faults.append(f"choices a float32 router does not make on the same "
                      f"inputs, by layer {router.tolist()}, over the limit "
                      f"of {limit:.0f} a layer")
    # everything before the router: against the reference's choices
    differ = _differ(mine, theirs)
    limit = limits["routing_differ_share_max"] * per_layer
    if (differ > limit).any():
        faults.append(f"choices that differ from the reference's, by layer "
                      f"{differ.tolist()}, over the limit of {limit:.0f} a "
                      f"layer")
    return faults, router, differ


def _checked(ref, tokens, *, micro, config):
    """The reference's loss and gradient norm, or NaN in their place where
    the routing of the same batch is outside the configuration's limits."""
    import numpy as np
    from horovod_tpu import tracing
    kwargs = shapes(config)
    want = sdar_moe_ref.loss_and_grad_norm(ref, tokens, micro=micro,
                                           **kwargs)
    sizes, mine, inputs = routing_of(program_config(config),
                                     system_tree(ref), tokens)
    tracing.routing_load(PROGRAM, sizes)
    again = sdar_moe_ref.router_choices(
        inputs, ref["h"]["moe"]["router"], top_k=kwargs["top_k"],
        norm_topk=kwargs["norm_topk"])
    del inputs
    theirs = np.concatenate([
        np.asarray(sdar_moe_ref.choices(ref, tokens[i:i + micro], **kwargs))
        for i in range(0, tokens.shape[0], micro)], axis=1)
    sizes = np.asarray(sizes)
    faults, router, differ = routing_faults(
        mine, theirs, again, sizes, kwargs["experts_first"], config["check"])
    print(f"[sdar_moe] routing of the check batch: rows the held experts "
          f"were given, by layer {sizes.sum(1).tolist()} (busiest expert "
          f"over the mean {sizes.max() / sizes.mean():.3f}), each counted "
          f"again from the choices; of {np.asarray(mine)[0].size} choices "
          f"a layer, those a float32 router does not make on the same "
          f"inputs, by layer {router.tolist()}; those the reference did "
          f"not make, by layer {differ.tolist()}", flush=True)
    if faults:
        print(f"[sdar_moe] ROUTING OUTSIDE ITS LIMITS: {'; '.join(faults)}. "
              f"The reference read loss {want[0]:.6f} grad norm "
              f"{want[1]:.6f}; NaN goes to the driver's check in their "
              f"place, which fails", flush=True)
        return float("nan"), float("nan")
    return want


reference = types.SimpleNamespace(loss_and_grad_norm=_checked)
train_flops_per_token = flops_sdar.train_flops_per_token
