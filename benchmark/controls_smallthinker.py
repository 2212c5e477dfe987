#!/usr/bin/env python3
"""The second reading of the limits of ``smallthinker``: what the check must
not let through, put through the comparison the cell's check makes.

    python3 benchmark/controls_smallthinker.py <seed> [<seed> ...]

On the cell's own weights and batch 0 of every seed, judged as
``drivers/train_steps.check`` and ``families/smallthinker._checked`` judge
the cell: the loss and gradient norm against the float32 reference's inside
the traffic file's tolerances, the routing of every layer by
``families.sdar_moe.routing_faults`` and every layer's attention output by
``families.smallthinker.attention_faults`` against the configuration's
``check`` block. The system must pass all; each of seven controls must fail
at least the one it is aimed at: the system's rows with one assignment taken
away; the system's own router inputs routed with the logits in bfloat16; the
program with its **window layers run causal**, with **RoPE on the global
layer**, with **RoPE left off a window layer** (the program itself under
another layout); the reference with its **router fed the normed stream after
attention** and with **silu for relu** (the program has no such knob, so the
model that must not pass is computed by the reference's own departures and
judged against the reference proper, as ``controls_lfm2.py`` judges a
bfloat16 reference). On a TPU at the configuration's size, and writes the
readings to ``chiprun_out/controls_smallthinker.json``; ``JAX_PLATFORMS=cpu``
rehearses the code at a tiny one and writes nothing.
"""

import dataclasses
import json
import os
import sys
import time

# the frame controls_lfm2.py has outside its main(): where the benchmark is
from controls_lfm2 import BENCH, ROOT

TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, moe_ffn_hidden_size=16, moe_num_primary_experts=2,
            moe_num_active_primary_experts=2, sliding_window_size=8,
            vocab_size=256)


def readings(config, traffic, rows, T, seeds):
    """``{"<seed>:<side>": reading}`` of the system and of every control on
    batch 0 of ``seeds`` at ``rows x T``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from families import smallthinker as fam
    from reference import smallthinker_ref as ref
    cfg, kw, limits = (fam.program_config(config), fam.shapes(config),
                       config["check"])
    first, held, top_k = (kw["experts_first"],
                          config["moe_num_primary_experts"], kw["top_k"])
    params = fam.make_params(cfg, 0, "float32")
    tree = ref.from_system(params, cfg.num_layers)
    routers = fam.routers_of(tree)
    layers = range(cfg.num_layers)
    globals_, windows = ([i for i in layers if not cfg.rope_layout[i]],
                         [i for i in layers if cfg.rope_layout[i]])

    def program(**other):
        """``tokens -> (loss, gradient norm)`` of the program, or of the
        program under another layout."""
        mdl = fam.model(dataclasses.replace(cfg, **other))

        @jax.jit
        def run(params, tokens):
            value, grads = jax.value_and_grad(
                lambda p: fam.loss_fn(mdl, p, tokens))(params)
            return value, jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads)))
        return lambda tokens: tuple(float(x) for x in run(params, tokens))

    flip = lambda layout, where, to: tuple(
        to if i in where else x for i, x in enumerate(layout))
    layouts = {
        "system": {},
        "system_window_layers_run_causal": dict(
            sliding_window_layout=(0,) * cfg.num_layers),
        "system_rope_on_the_global_layer": dict(
            rope_layout=flip(cfg.rope_layout, globals_[:1], 1)),
        "system_rope_left_off_a_window_layer": dict(
            rope_layout=flip(cfg.rope_layout, windows[:1], 0))}
    departures = {
        "ref_router_fed_the_normed_stream_after_attention": dict(
            route_after=True),
        "ref_silu_for_relu": dict(act="silu")}

    def recount(choice):
        local = np.asarray(choice) - first
        return np.stack([np.bincount(l[(l >= 0) & (l < held)],
                                     minlength=held) for l in local])

    def off_limits(got, want):
        """The driver's comparison of loss and gradient norm."""
        faults = []
        for what, a, b, tol in (
                ("loss", got[0], want[0], traffic["loss_rel_tol"]),
                ("grad norm", got[1], want[1],
                 traffic["grad_norm_rel_tol"])):
            rel = abs(a - b) / abs(b)
            if not (np.isfinite(a) and rel <= tol):
                faults.append(f"{what} {a:.6f} is {rel:.2e} from the "
                              f"reference's {b:.6f}, over {tol:.0e}")
        return faults

    out = {}
    for seed in seeds:
        t0 = time.perf_counter()
        rng = np.random.default_rng([seed, 0])
        tokens = jnp.asarray(rng.integers(0, config["vocab_size"], (rows, T),
                                          dtype=np.int32))
        want = ref.loss_and_grad_norm(tree, tokens, micro=1, **kw)
        theirs, their_attn = fam.reference_look(tree, tokens, micro=1, **kw)
        # side: (loss and gradient norm, or None where the routing alone is
        # changed; its choices, its own router inputs routed again in
        # float32, its rows, its attention outputs)
        sides = {}
        for name, other in layouts.items():
            lean = dataclasses.replace(cfg, **other)
            sizes, mine, inputs, attn = fam.routing_of(lean, params, tokens)
            sizes, mine = np.asarray(sizes), np.asarray(mine)
            route = lambda **extra: np.asarray(ref.router_choices(
                inputs, routers, top_k=top_k, norm_topk=kw["norm_topk"],
                **extra))
            again = route()
            sides[name] = (program(**other)(tokens), mine, again, sizes,
                           np.asarray(attn))
            if name == "system":
                dropped = sizes.copy()
                dropped[-1, 0] -= 1
                sides["system_one_assignment_dropped"] = (
                    None, mine, again, dropped, sides[name][4])
                c = route(router_dtype="bfloat16")
                sides["system_router_logits_bf16"] = (
                    None, c, again, recount(c), sides[name][4])
            del inputs, attn
        for name, extra in departures.items():
            got = ref.loss_and_grad_norm(tree, tokens, micro=1, **kw,
                                         **extra)
            look = [ref.choices(tree, tokens[i:i + 1], with_inputs=True,
                                **kw, **extra) for i in range(rows)]
            c = np.concatenate([np.asarray(x[0]) for x in look], axis=1)
            u = jnp.concatenate([x[1] for x in look], axis=1)
            a = np.concatenate([np.asarray(x[2]) for x in look], axis=1)
            del look
            again = np.asarray(ref.router_choices(
                u, routers, top_k=top_k, norm_topk=kw["norm_topk"]))
            del u
            sides[name] = (got, c, again, recount(c), a)
        for name, (got, c, a, s, attn) in sides.items():
            faults, router, differ = fam.routing_faults(c, theirs, a, s,
                                                        first, limits)
            more, apart = fam.attention_faults(attn, their_attn, limits)
            faults += more
            numbers = ""
            if got is not None:
                faults = off_limits(got, want) + faults
                numbers = (f"loss {got[0]:.6f} ({abs(got[0] / want[0] - 1):.2e}"
                           f" from the reference), grad norm {got[1]:.6f} "
                           f"({abs(got[1] / want[1] - 1):.2e}); ")
            print(f"{seed} {name}: {numbers}of {c[0].size} choices a layer, "
                  f"against a float32 router on the same inputs "
                  f"{router.tolist()}, against the reference "
                  f"{differ.tolist()}; attention outputs from the "
                  f"reference's {[round(float(x), 5) for x in apart]}: "
                  f"{'FAILS ' + '; '.join(faults) if faults else 'passes'}",
                  flush=True)
            out[f"{seed}:{name}"] = {
                "loss_and_grad_norm": got, "reference": want,
                "router_differ": router.tolist(), "differ": differ.tolist(),
                "attention_differ": [float(x) for x in apart],
                "of": int(c[0].size), "faults": faults,
                "rows": s.sum(1).tolist()}
        print(f"{seed}: {time.perf_counter() - t0:.1f} s", flush=True)
    return out


def main(seeds):
    from horovod_tpu.utils import compile_cache
    compile_cache.enable()
    with open(os.path.join(BENCH, "configs",
                           "smallthinker-21b-a3b-train.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "train-fixed-2x16384.json")) as f:
        traffic = json.load(f)
    rows, T = traffic["sequences_per_chip"], traffic["seq_len"]
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if rehearsal:
        config.update(TINY)
        config["deployment"].update(router_width=8, experts_first=2)
        config["run"].update(compute_dtype="float32")
        rows, T = 2, 32
    out = readings(config, traffic, rows, T, seeds)
    if not rehearsal:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "controls_smallthinker.json"), "w") as f:
            json.dump(out, f, indent=1)
    # the system passes and every control fails, or the limits are wrong
    told_apart = all((not v["faults"]) == k.endswith(":system")
                     for k, v in out.items())
    return 0 if told_apart else 1


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [3700000019]))
