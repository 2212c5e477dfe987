#!/usr/bin/env python3
"""The second reading of the routing limits of ``lfm2_moe``: what the check
must not let through, put through the comparison the cell's check makes.

    python3 benchmark/controls_lfm2.py <seed> [<seed> ...]

On the cell's own weights and bias and batch 0 of every seed, judged by
``families.sdar_moe.routing_faults`` against the float32 reference and the
configuration's ``check`` block: the system's forward (must pass); the same
with one assignment taken from the rows it reports; the system's own router
inputs routed with the logits in bfloat16; the same inputs routed in float32
**without the selection bias**; the reference with bfloat16 router logits;
the reference in bfloat16 throughout (each must fail). On a TPU at the
configuration's size, and writes the counts to
``chiprun_out/controls_lfm2.json``; ``JAX_PLATFORMS=cpu`` rehearses the code
at a tiny one and writes nothing.
"""

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=48, moe_intermediate_size=16, num_experts=2,
            num_experts_per_tok=4, num_hidden_layers=3, num_dense_layers=1,
            layer_types=["conv", "full_attention", "conv"], vocab_size=256)


def main(seeds):
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.utils import compile_cache
    from families import lfm2_moe as fam
    from reference import lfm2_moe_ref as ref
    compile_cache.enable()
    with open(os.path.join(BENCH, "configs",
                           "lfm2-24b-a2b-train.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "train-fixed-4x8192.json")) as f:
        traffic = json.load(f)
    rows, T = traffic["sequences_per_chip"], traffic["seq_len"]
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if rehearsal:
        config.update(TINY)
        config["deployment"].update(router_width=8, experts_first=2)
        rows, T = 2, 32
    cfg, kw, limits = fam.program_config(config), fam.shapes(config), \
        config["check"]
    first, held, top_k = kw["experts_first"], config["num_experts"], \
        kw["top_k"]
    bias = fam.expert_bias(cfg)
    params = fam.make_params(cfg, 0, "float32")
    tree = ref.from_system(params, cfg.num_layers)
    routers, biases = fam.routers_of(tree, cfg)

    def recount(choice):
        local = np.asarray(choice) - first
        return np.stack([np.bincount(l[(l >= 0) & (l < held)],
                                     minlength=held) for l in local])

    def ref_choices(tokens, **extra):
        """``(choices, router inputs)`` of the reference, a row at a time."""
        got = [ref.choices(tree, tokens[i:i + 1], bias, with_inputs=True,
                           **kw, **extra) for i in range(tokens.shape[0])]
        return (np.concatenate([np.asarray(c) for c, _ in got], axis=1),
                jnp.concatenate([u for _, u in got], axis=1))

    out = {}
    for seed in seeds:
        t0 = time.perf_counter()
        rng = np.random.default_rng([seed, 0])
        tokens = jnp.asarray(rng.integers(0, config["vocab_size"], (rows, T),
                                          dtype=np.int32))
        theirs, _ = ref_choices(tokens)
        sizes, mine, inputs = fam.routing_of(cfg, params, tokens)
        sizes, mine = np.asarray(sizes), np.asarray(mine)
        again = np.asarray(ref.router_choices(inputs, routers, biases,
                                              top_k=top_k))
        # side: (its choices, its own inputs routed again in float32 with
        # the bias, rows)
        sides = {"system": (mine, again, sizes)}
        dropped = sizes.copy()
        dropped[-1, 0] -= 1
        sides["system_one_assignment_dropped"] = (mine, again, dropped)
        c = np.asarray(ref.router_choices(inputs, routers, biases,
                                          top_k=top_k,
                                          router_dtype="bfloat16"))
        sides["system_router_logits_bf16"] = (c, again, recount(c))
        c = np.asarray(ref.router_choices(inputs, routers, None,
                                          top_k=top_k))
        sides["system_router_without_the_bias"] = (c, again, recount(c))
        del inputs
        for name, extra in (
                ("ref_bf16_router_logits", {"router_dtype": "bfloat16"}),
                ("ref_bf16_throughout", {"dtype": "bfloat16"})):
            c, u = ref_choices(tokens, **extra)
            a = np.asarray(ref.router_choices(u, routers, biases,
                                              top_k=top_k))
            sides[name] = (c, a, recount(c))
            del u
        for name, (c, a, s) in sides.items():
            faults, router, differ = fam.routing_faults(c, theirs, a, s,
                                                        first, limits)
            print(f"{seed} {name}: of {c[0].size} choices a layer, against "
                  f"a float32 router with the bias on the same inputs "
                  f"{router.tolist()}, against the reference "
                  f"{differ.tolist()}: "
                  f"{'FAILS ' + '; '.join(faults) if faults else 'passes'}",
                  flush=True)
            out[f"{seed}:{name}"] = {
                "router_differ": router.tolist(), "differ": differ.tolist(),
                "of": int(c[0].size), "faults": faults,
                "rows": s.sum(1).tolist()}
        print(f"{seed}: {time.perf_counter() - t0:.1f} s", flush=True)
    if not rehearsal:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "controls_lfm2.json"),
                  "w") as f:
            json.dump(out, f, indent=1)
    # the system passes and every control fails, or the limits are wrong
    told_apart = all((not v["faults"]) == k.endswith(":system")
                     for k, v in out.items())
    return 0 if told_apart else 1


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [3000000019]))
