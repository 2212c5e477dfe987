"""GPT-2 trained with pipeline parallelism (GPipe schedule over a ``pp``
mesh axis): transformer blocks staged across devices, microbatches streamed
through ``ppermute`` hops, loss masked to the last stage inside
``pipeline_loss`` so gradients need no caller-side scaling.

Run (virtual 8-device CPU mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/gpt2_pipeline.py --stages 8 --microbatches 8
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.gpt2 import GPT2, GPT2Config
from horovod_tpu.models.gpt2_pipeline import (gpt2_pp_loss_and_grad,
                                              stack_block_params)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=None,
                    help="pipeline stages (default: all devices)")
    ap.add_argument("--interleave", type=int, default=0, metavar="R",
                    help="use the circular schedule with R rounds per "
                         "device (model depth = stages*R*layers-per-stage; "
                         "requires microbatches <= stages)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width inside every stage "
                         "(Megatron-in-GPipe; devices = stages * tp)")
    ap.add_argument("--layers-per-stage", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--microbatch-size", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--lr", type=float, default=0.1)
    args = ap.parse_args()

    hvd.init(axis_name="pp")
    TP = max(args.tp, 1)
    S = args.stages or hvd.size() // TP
    if S < 1 or S * TP > len(jax.devices()):
        raise SystemExit(
            f"--stages {S} x --tp {TP} does not fit the "
            f"{len(jax.devices())} available devices")
    if hvd.size() != S * TP:
        hvd.init(devices=jax.devices()[:S * TP], axis_name="pp")

    R = max(args.interleave, 0)
    layers = S * args.layers_per_stage * (R or 1)
    cfg = GPT2Config(vocab_size=256, max_seq_len=args.seq,
                     num_layers=layers, num_heads=4,
                     d_model=args.d_model, dtype=jnp.float32)
    M, mb, T = args.microbatches, args.microbatch_size, args.seq
    if R:
        if M > S:
            raise SystemExit(
                f"--interleave requires --microbatches ({M}) <= stages "
                f"({S}); chunk the batch and accumulate gradients instead")
        bubble = 1 - R * M / (M + R * S - 1)
        print(f"stages={S} rounds={R} layers={layers} microbatches={M} "
              f"-> bubble {bubble:.1%} (circular)")
    else:
        bubble = (S - 1) / (M + S - 1)
        print(f"stages={S} layers/stage={args.layers_per_stage} "
              f"microbatches={M} -> bubble {bubble:.1%} (GPipe)")

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (M, mb, T)),
                         jnp.int32)
    params = GPT2(cfg).init(jax.random.PRNGKey(0),
                            tokens.reshape(M * mb, T))["params"]
    if TP > 1:
        # Megatron-in-GPipe: every stage's matmuls head/feature-split over
        # a tp mesh axis (f/g conjugate ops inside the stage body).
        from horovod_tpu.models.gpt2_pipeline import (
            block_specs_tp, gpt2_pp_tp_loss_and_grad,
            gpt2_pp_tp_loss_and_grad_interleaved, make_pp_tp_params,
            make_pp_tp_params_interleaved)
        from horovod_tpu.parallel import make_mesh
        if R:
            blocks, rest = make_pp_tp_params_interleaved(
                params, S, R, cfg.num_heads)
            grad_step = gpt2_pp_tp_loss_and_grad_interleaved(cfg, "pp",
                                                             "tp")
            specs = block_specs_tp("pp", "tp", extra_dims=1)
        else:
            blocks, rest = make_pp_tp_params(params, S, cfg.num_heads)
            grad_step = gpt2_pp_tp_loss_and_grad(cfg, "pp", "tp")
            specs = block_specs_tp("pp", "tp")

        mesh = make_mesh({"pp": S, "tp": TP},
                         devices=jax.devices()[:S * TP])
        print(f"tensor-parallel width tp={TP} inside every stage")
    elif R:
        from horovod_tpu.models.gpt2_pipeline import (
            stack_block_params_interleaved,
            gpt2_pp_loss_and_grad_interleaved)
        blocks, rest = stack_block_params_interleaved(params, S, R)
        grad_step = gpt2_pp_loss_and_grad_interleaved(cfg, axis_name="pp")
    else:
        blocks, rest = stack_block_params(params, S)
        grad_step = gpt2_pp_loss_and_grad(cfg, axis_name="pp")

    def train_step(blocks, rest, tokens):
        loss, g_blocks, g_rest = grad_step(blocks, rest, tokens)
        blocks = jax.tree_util.tree_map(
            lambda p, g: p - args.lr * g, blocks, g_blocks)
        rest = jax.tree_util.tree_map(
            lambda p, g: p - args.lr * g, rest, g_rest)
        return loss, blocks, rest

    if TP > 1:
        fn = jax.jit(jax.shard_map(
            train_step, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=(P(), specs, P()), check_vma=False))
    else:
        fn = hvd.spmd(train_step,
                      in_specs=(P("pp"), P(), P()),
                      out_specs=(P(), P("pp"), P()))
    for step in range(args.steps):
        loss, blocks, rest = fn(blocks, rest, tokens)
        print(f"step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
