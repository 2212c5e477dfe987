#!/usr/bin/env python3
"""The second reading of the limits of ``glm4_moe_lite``: what the check must
not let through, put through the comparison the cell's check makes.

    python3 benchmark/controls_glm4.py <seed> [<seed> ...]

On the cell's own weights and bias and batch 0 of every seed, judged as
``drivers/train_steps.check`` and ``families/glm4_moe_lite._checked`` judge
the cell: the program's loss and gradient norm against the float32
reference's inside the traffic file's tolerances, and the routing of the
routed trunk layers and the module's block by
``families.sdar_moe.routing_faults`` against the configuration's ``check``
block. The system must pass both; each of five controls must fail the one it
is aimed at: the system's rows with one assignment taken away; the system's
own router inputs routed with the logits in bfloat16; the same inputs routed
in float32 **without the selection bias**; the program **without its shared
expert**; the program's loss **without the module's term**. On a TPU at the
configuration's size, and writes the readings to
``chiprun_out/controls_glm4.json``; ``JAX_PLATFORMS=cpu`` rehearses the code
at a tiny one and writes nothing.
"""

import dataclasses
import json
import os
import sys
import time

# the frame controls_lfm2.py has outside its main(): where the benchmark is
from controls_lfm2 import BENCH, ROOT

TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
            qk_rope_head_dim=4, v_head_dim=16, intermediate_size=48,
            moe_intermediate_size=16, n_routed_experts=2,
            num_hidden_layers=3, vocab_size=256)


def main(seeds):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.utils import compile_cache
    from families import glm4_moe_lite as fam
    from reference import glm4_moe_lite_ref as ref
    compile_cache.enable()
    with open(os.path.join(BENCH, "configs",
                           "glm-4.7-flash-train.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "train-fixed-2x8192.json")) as f:
        traffic = json.load(f)
    rows, T = traffic["sequences_per_chip"], traffic["seq_len"]
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if rehearsal:
        config.update(TINY)
        config["deployment"].update(router_width=8, experts_first=2)
        config["run"].update(compute_dtype="float32")
        rows, T = 2, 32
    cfg, kw, limits = (fam.program_config(config), fam.shapes(config),
                       config["check"])
    first, held, top_k = (kw["experts_first"], config["n_routed_experts"],
                          kw["top_k"])
    bias = fam.expert_bias(cfg)
    params = fam.make_params(cfg, 0, "float32")
    tree = ref.from_system(params, cfg.num_layers)
    routers, biases = fam.routers_of(tree, cfg)

    def program(**without):
        """``tokens -> (loss, gradient norm)`` of the program, or of the
        program without a part of it."""
        lean = dataclasses.replace(cfg, **without)
        mdl = fam.model(lean)
        b = bias[:lean.num_layers + lean.mtp]

        @jax.jit
        def run(params, tokens):
            value, grads = jax.value_and_grad(
                lambda p: fam.loss_fn(mdl, p, tokens, b))(params)
            return value, jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads)))
        return lambda tokens: tuple(float(x) for x in run(params, tokens))

    programs = {"system": program(),
                "system_without_the_shared_expert": program(shared_experts=0),
                "system_loss_without_the_mtp_term": program(mtp=0)}

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
        # the program's side first: it needs the room the reference takes
        read = {name: run(tokens) for name, run in programs.items()}
        want = ref.loss_and_grad_norm(tree, tokens, bias, micro=1, **kw)
        sizes, mine, inputs = fam.routing_of(cfg, params, tokens)
        sizes, mine = np.asarray(sizes), np.asarray(mine)
        theirs = fam.reference_choices(tree, tokens, mine, micro=1,
                                       expert_bias=bias, **kw)
        route = lambda b, **extra: np.asarray(ref.router_choices(
            inputs, routers, b, top_k=top_k, **extra))
        again = route(biases)
        # side: (loss and gradient norm, or None where the routing alone is
        # changed; its choices, its own inputs routed again in float32 with
        # the bias, its rows)
        sound = (mine, again, sizes)
        sides = {"system": (read["system"],) + sound}
        dropped = sizes.copy()
        dropped[-1, 0] -= 1
        sides["system_one_assignment_dropped"] = (None, mine, again, dropped)
        c = route(biases, router_dtype="bfloat16")
        sides["system_router_logits_bf16"] = (None, c, again, recount(c))
        c = route(None)
        sides["system_router_without_the_bias"] = (None, c, again,
                                                   recount(c))
        del inputs
        for name in list(programs)[1:]:
            sides[name] = (read[name],) + sound
        for name, (got, c, a, s) in sides.items():
            faults, router, differ = fam.routing_faults(c, theirs, a, s,
                                                        first, limits)
            numbers = ""
            if got is not None:
                faults = off_limits(got, want) + faults
                numbers = (f"loss {got[0]:.6f} ({abs(got[0] / want[0] - 1):.2e}"
                           f" from the reference), grad norm {got[1]:.6f} "
                           f"({abs(got[1] / want[1] - 1):.2e}); ")
            print(f"{seed} {name}: {numbers}of {c[0].size} choices a layer, "
                  f"against a float32 router with the bias on the same "
                  f"inputs {router.tolist()}, against the reference "
                  f"{differ.tolist()}: "
                  f"{'FAILS ' + '; '.join(faults) if faults else 'passes'}",
                  flush=True)
            out[f"{seed}:{name}"] = {
                "loss_and_grad_norm": got, "reference": want,
                "router_differ": router.tolist(), "differ": differ.tolist(),
                "of": int(c[0].size), "faults": faults,
                "rows": s.sum(1).tolist()}
        print(f"{seed}: {time.perf_counter() - t0:.1f} s", flush=True)
    if not rehearsal:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "controls_glm4.json"),
                  "w") as f:
            json.dump(out, f, indent=1)
    # the system passes and every control fails, or the limits are wrong
    told_apart = all((not v["faults"]) == k.endswith(":system")
                     for k, v in out.items())
    return 0 if told_apart else 1


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [3300000019]))
