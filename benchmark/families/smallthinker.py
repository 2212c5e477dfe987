"""SmallThinker (``model_name: smallthinker_*``): from a configuration file to
the program's decoder (global attention without positions beside
sliding-window attention with RoPE, a router that reads the block's input,
ReGLU experts) and to the reference. The one place that knows both the
file's keys (the publisher's names, and ``deployment`` / ``assumed`` for the
chip's share and what the publisher does not give) and the program's
(``SmallThinkerConfig``).

The weights are the same in every run: they stand in for the one checkpoint
that is being trained on. ``--seed`` draws the tokens.

The check also judges the routing, by ``families/sdar_moe.routing_faults``
and for its reasons (``families/lfm2_moe.py`` gives them): after the
reference's loss and gradient the family runs the program's forward once
more on the check batch and the initial weights with its auxiliary outputs
kept, and holds it to what the configuration's ``check`` block states: every
choice that fell on a held expert has its row in the grouped products
(counted again, exactly); of a layer's choices no more than a stated share
are ones that a float32 router would not make on the program's own router
inputs, **which here are the blocks' inputs**: the embedding rows for layer
0, the output of the block before for the others; no more than a stated
share differ from the reference's. And it judges the attention, which the
loss and the gradient norm of 32,768 positions hardly see at random weights
(a layer's attention output is a small part of the stream): what each
layer's attention gave, the output projection included, may differ from the
reference's by no more than a stated share of its norm
(:func:`attention_faults`). Outside any limit the reference's loss comes
back as NaN and the driver's check fails. ``controls_smallthinker.py`` puts
a dropped row, bfloat16 logits, window layers run causal, RoPE on the global
layer, RoPE left off a window layer, a router fed the normed stream after
attention and silu for relu through the same comparison. The same forward
sets the gauges ``moe_local_assignments`` / ``moe_load_max_over_mean``.
After the window, never in it.
"""

import dataclasses
import types

import numpy as np

import flops_smallthinker
from families.gpt2 import key
from families.sdar_moe import routing_faults
from reference import smallthinker_ref
# a program without the family's model ends here, before the chip is asked
from horovod_tpu.models.smallthinker import (Attention, Block, SmallThinker,
                                             SmallThinkerConfig, loss_fn)

PROGRAM = "train_step"      # the name hvd.spmd gives the driver's step
# The weights are the same in every run and ``--seed`` draws the tokens: a
# dropless share's step time follows its routers (families/sdar_moe.py,
# WEIGHTS_SEED; PERF.md, Findings PRs 27 and 31), and the configuration file
# says so.
WEIGHTS_SEED = 37


def program_config(config, **overrides):
    import jax.numpy as jnp
    run, assumed, deployment = (config["run"], config["assumed"],
                                config["deployment"])
    if (config["tie_word_embeddings"] or config["rope_scaling"] is not None
            or not config["moe_primary_router_apply_softmax"]):
        raise ValueError(
            "the program's decoder has an untied head, plain RoPE and a "
            "softmax over the router's logits before the top-k: the "
            "configuration asks for something else")
    fields = dict(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        sliding_window_layout=tuple(config["sliding_window_layout"]),
        rope_layout=tuple(config["rope_layout"]),
        sliding_window=config["sliding_window_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_model=config["hidden_size"],
        d_expert=config["moe_ffn_hidden_size"],
        experts_total=deployment["router_width"],
        experts_held=(deployment["experts_first"],
                      config["moe_num_primary_experts"]),
        top_k=config["moe_num_active_primary_experts"],
        norm_topk=config["norm_topk_prob"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"], embed_std=assumed["embedding_std"],
        dtype=jnp.dtype(run["compute_dtype"]),
        attention=run.get("attention", "dense"),
        remat=run.get("remat", False),
        remat_policy=run.get("remat_policy", "full"))
    fields.update(overrides)
    return SmallThinkerConfig(**fields)


def model(cfg):
    return SmallThinker(cfg)


def loss(mdl, params, tokens):
    return loss_fn(mdl, params, tokens)


def make_params(cfg, seed, dtype, sharding=None):
    """Seeded random weights in ``dtype``, made on the device in one jitted
    call, through the dense, un-remat twin on a short row: the parameter
    tree is the same and no kernel is compiled to trace shapes. From
    ``WEIGHTS_SEED`` in every run and not from the run's ``seed``, which
    draws this cell's data: see the constant, and the line this prints."""
    import jax
    import jax.numpy as jnp
    twin = SmallThinker(dataclasses.replace(cfg, attention="dense",
                                            remat=False))

    def init_params(k):
        tree = twin.init(k, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    print(f"[smallthinker] weights from the fixed seed {WEIGHTS_SEED} "
          f"(configuration, assumed.weights); --seed {seed} draws the "
          f"tokens", flush=True)
    return jax.jit(init_params, out_shardings=sharding)(key(WEIGHTS_SEED))


def reference_tree(config, params):
    return smallthinker_ref.from_system(params, config["num_hidden_layers"])


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
    return {"sliding_window_layout": tuple(config["sliding_window_layout"]),
            "rope_layout": tuple(config["rope_layout"]),
            "sliding_window": config["sliding_window_size"],
            "num_heads": config["num_attention_heads"],
            "num_kv_heads": config["num_key_value_heads"],
            "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "top_k": config["moe_num_active_primary_experts"],
            "norm_topk": config["norm_topk_prob"],
            "experts_first": config["deployment"]["experts_first"]}


def routing_of(cfg, params, tokens):
    """The program's forward on ``tokens`` with its auxiliary outputs kept:
    ``(group_sizes (L, held), choice (L, B, T, top_k), inputs (L, B, T, d),
    attention (L, B, T, d))`` of every layer: the rows each held expert was
    given, the experts every position chose, what the router chose them
    from (the block's input: the embedding's rows, then the block before's
    output, in the compute dtype) and what the layer's attention added to
    the stream."""
    import jax
    import jax.numpy as jnp
    mdl = SmallThinker(dataclasses.replace(cfg, remat=False))

    @jax.jit
    def look(params, tokens):
        _, kept = mdl.apply(
            {"params": params}, tokens, mutable=["intermediates"],
            capture_intermediates=lambda m, _: isinstance(
                m, (Block, Attention)))
        layers = [kept["intermediates"][f"h{i}"]
                  for i in range(cfg.num_layers)]
        outputs = [h["__call__"][0] for h in layers]
        first = params["wte"][tokens].astype(cfg.dtype)
        return (jnp.stack([h["moe"]["group_sizes"][0] for h in layers]),
                jnp.stack([h["moe"]["choice"][0].reshape(
                    *tokens.shape, cfg.top_k) for h in layers]),
                jnp.stack([first] + outputs[:-1]),
                jnp.stack([h["attn"]["__call__"][0] for h in layers]))

    return look(params, tokens)


def routers_of(ref):
    """``routers (L, d, experts)``, for ``router_choices``."""
    import jax.numpy as jnp
    return jnp.stack([block["moe"]["router"] for block in ref["h"]])


def reference_look(ref, tokens, *, micro, **kwargs):
    """``(choices (L, B, T, top_k), attention (L, B, T, d))`` of the
    reference on ``tokens``, a micro-batch at a time; ``kwargs`` may hold
    the departures ``smallthinker_ref`` computes for the controls."""
    got = [smallthinker_ref.choices(ref, tokens[i:i + micro],
                                    with_inputs=True, **kwargs)
           for i in range(0, tokens.shape[0], micro)]
    return (np.concatenate([np.asarray(c) for c, _, _ in got], axis=1),
            np.concatenate([np.asarray(a) for _, _, a in got], axis=1))


def attention_differs(mine, theirs):
    """By layer: the norm of what the judged side's attention gave less the
    reference's, over the norm of the reference's."""
    mine, theirs = (np.asarray(a, np.float32) for a in (mine, theirs))
    L = theirs.shape[0]
    gap = np.linalg.norm((mine - theirs).reshape(L, -1), axis=1)
    return gap / np.linalg.norm(theirs.reshape(L, -1), axis=1)


def attention_faults(mine, theirs, limits):
    """What of one batch's attention outputs lies outside ``limits`` (the
    configuration's ``check`` block), as a list of sentences (empty: sound),
    and the relative differences by layer."""
    differ = attention_differs(mine, theirs)
    limit = limits["attention_differ_max"]
    faults = []
    if not (differ <= limit).all():
        faults.append(f"attention outputs that differ from the reference's "
                      f"by {[round(float(x), 4) for x in differ]} of their "
                      f"norm, by layer, over the limit of {limit}")
    return faults, differ


def _checked(ref, tokens, *, micro, config):
    """The reference's loss and gradient norm, or NaN in their place where
    the routing or the attention of the same batch is outside the
    configuration's limits."""
    from horovod_tpu import tracing
    kwargs, cfg = shapes(config), program_config(config)
    want = smallthinker_ref.loss_and_grad_norm(ref, tokens, micro=micro,
                                               **kwargs)
    sizes, mine, inputs, attn = routing_of(cfg, system_tree(ref), tokens)
    tracing.routing_load(PROGRAM, sizes)
    again = smallthinker_ref.router_choices(
        inputs, routers_of(ref), top_k=kwargs["top_k"],
        norm_topk=kwargs["norm_topk"])
    del inputs
    sizes, mine, attn = np.asarray(sizes), np.asarray(mine), np.asarray(attn)
    theirs, their_attn = reference_look(ref, tokens, micro=micro, **kwargs)
    faults, router, differ = routing_faults(
        mine, theirs, again, sizes, kwargs["experts_first"], config["check"])
    more, apart = attention_faults(attn, their_attn, config["check"])
    print(f"[smallthinker] routing of the check batch: rows the held "
          f"experts were given, by layer {sizes.sum(1).tolist()} (busiest "
          f"expert over the mean {sizes.max() / sizes.mean():.3f}), each "
          f"counted again from the choices; of {mine[0].size} choices a "
          f"layer, those a float32 router does not make on the same inputs, "
          f"by layer {router.tolist()}; those the reference did not make, "
          f"by layer {differ.tolist()}; attention outputs from the "
          f"reference's, of their norm, by layer "
          f"{[round(float(x), 5) for x in apart]}", flush=True)
    faults += more
    if faults:
        print(f"[smallthinker] OUTSIDE ITS LIMITS: {'; '.join(faults)}. The "
              f"reference read loss {want[0]:.6f} grad norm {want[1]:.6f}; "
              f"NaN goes to the driver's check in their place, which fails",
              flush=True)
        return float("nan"), float("nan")
    return want


reference = types.SimpleNamespace(loss_and_grad_norm=_checked)
train_flops_per_token = flops_smallthinker.train_flops_per_token
