"""Driver ``train_steps``: optimizer steps on fresh seeded batches.

The README path of the trainer — ``hvd.value_and_grad`` and
``hvd.DistributedOptimizer`` under ``hvd.spmd`` with donated state — over
the cell's chips, data parallel. The traffic file gives the rows per chip
and their length; the global batch is rows x chips. Every step gets a new
batch drawn from the seed, put on the device while the previous step runs.
The window counts whole steps, each ended by ``block_until_ready``.

The check: the first warm-up step runs on the initial parameters and also
returns the norm of its (averaged) gradients; after the window the initial
parameters are made again from the seed and the plain fp32 reference gives
the loss and the gradient norm of the same batch. After the window and not
before it, so that the allocator's peak, read in between, is the
program's own.
"""

import time
from types import SimpleNamespace

import numpy as np


def _batch(ctx, rows, index):
    """Batch ``index`` of this seed: uniform over the published vocabulary."""
    rng = np.random.default_rng([ctx.seed, index])
    return rng.integers(0, ctx.config["vocab_size"],
                        (rows, ctx.traffic["seq_len"]), dtype=np.int32)


def set_up(ctx):
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    hvd, fam, traffic = ctx.hvd, ctx.family, ctx.traffic

    hvd.init(devices=ctx.devices)
    if hvd.size() != len(ctx.devices):
        raise RuntimeError(f"hvd.size() == {hvd.size()}")
    ctx.log(f"train: hvd.init done, topology {hvd.topology()}")
    cfg = fam.program_config(ctx.config)
    model = fam.model(cfg)
    rows = traffic["sequences_per_chip"] * len(ctx.devices)
    run = ctx.config["run"]
    opt = hvd.DistributedOptimizer(
        getattr(optax, run["optimizer"])(run["learning_rate"]))

    def train_step(params, opt_state, tokens):
        loss, grads = hvd.value_and_grad(
            lambda p: fam.loss(model, p, tokens))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state, loss,
                optax.global_norm(grads))

    data = hvd.spmd_data_sharding()
    step = hvd.spmd(train_step, in_specs=(P(), P(), data.spec),
                    out_specs=(P(), P(), P(), P()), donate_argnums=(0, 1))
    replicated = NamedSharding(hvd.mesh(), P())
    make = lambda: fam.make_params(cfg, ctx.seed, run["param_dtype"],
                                   replicated)
    params = make()
    opt_state = jax.jit(opt.init, out_shardings=replicated)(params)
    put = lambda index: jax.device_put(_batch(ctx, rows, index), data)
    jax.block_until_ready(opt_state)
    ctx.log(f"train: state on the device; {len(ctx.devices)} device(s), global batch {rows} x "
            f"{traffic['seq_len']}, {cfg.num_layers} layers d{cfg.d_model} "
            f"vocab {cfg.vocab_size}, attention {cfg.attention}, remat "
            f"{cfg.remat}/{cfg.remat_policy}")

    if ctx.trace:
        # one more load of the executable; information, traced runs only
        mem = step.lower(params, opt_state, put(0)).compile().memory_analysis()
        ctx.log(f"train: compiled step memory_analysis: arguments "
                f"{mem.argument_size_in_bytes} output "
                f"{mem.output_size_in_bytes} alias {mem.alias_size_in_bytes} "
                f"temporaries {mem.temp_size_in_bytes} generated code "
                f"{mem.generated_code_size_in_bytes} bytes")

    # the check step: batch 0 on the initial parameters
    params, opt_state, loss, gnorm = step(params, opt_state, put(0))
    first = (float(loss), float(gnorm))
    programs = ctx.compiles.programs
    for i in range(1, traffic["warmup_steps"]):
        params, opt_state, loss, _ = step(params, opt_state, put(i))
    jax.block_until_ready((params, opt_state, loss))
    if ctx.compiles.programs != programs:
        raise RuntimeError("the train step compiled more than once")
    ctx.log(f"train: warm after {traffic['warmup_steps']} steps, first loss "
            f"{first[0]:.4f} grad norm {first[1]:.4f}")
    return SimpleNamespace(step=step, params=params, opt_state=opt_state,
                           put=put, rows=rows, make=make, first=first,
                           next_batch=traffic["warmup_steps"])


def window(ctx, st, seconds):
    jax, tracer = ctx.jax, ctx.tracer
    losses, ends, pending, done = [], [], None, 0
    index = st.next_batch
    tokens = st.put(index)
    t0 = t_end = time.perf_counter()
    while True:
        with tracer.span("bench:dispatch"):
            st.params, st.opt_state, loss, _ = st.step(
                st.params, st.opt_state, tokens)
        with tracer.span("bench:put_batch"):
            index += 1
            tokens = st.put(index)
        if pending is not None:
            with tracer.span("bench:wait"):
                pending.block_until_ready()
            done += 1
            t_end = time.perf_counter()
            ends.append(t_end)
        losses.append(loss)
        pending = loss
        tracer.tick(t_end - t0)
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready((st.params, st.opt_state, pending))
    done += 1
    t_end = time.perf_counter()
    tracer.stop()

    losses = [float(x) for x in losses]
    failed = sum(1 for x in losses if not np.isfinite(x))
    elapsed = t_end - t0
    tokens_done = done * st.rows * ctx.traffic["seq_len"]
    rate = tokens_done / elapsed / len(ctx.devices)
    ctx.log(f"train: {done} steps in {elapsed:.3f} s, {tokens_done} tokens, "
            f"step {elapsed / done * 1e3:.2f} ms, loss {losses[0]:.4f} -> "
            f"{losses[-1]:.4f}")
    # where a slow run lost its time: evenly, or in a few stalls
    gaps = sorted(b - a for a, b in zip(ends, ends[1:]))
    if gaps:
        mid = gaps[len(gaps) // 2]
        ctx.log(f"train: step ends apart by min {gaps[0] * 1e3:.2f} median "
                f"{mid * 1e3:.2f} max {gaps[-1] * 1e3:.2f} ms; "
                f"{sum(1 for g in gaps if g > 1.5 * mid)} over 1.5x the "
                f"median")
    return {"attempted": done, "failed": failed,
            "end_to_end": {"train_tokens_per_s_chip": rate},
            "counters": {"steps": done, "elapsed_s": elapsed,
                         "tokens_per_step": st.rows * ctx.traffic["seq_len"],
                         "seq_len": ctx.traffic["seq_len"]}}


def check(ctx, st):
    fam, traffic = ctx.family, ctx.traffic
    st.params = st.opt_state = None         # room for the reference
    initial = ctx.jax.tree_util.tree_map(
        lambda x: x.addressable_shards[0].data, st.make())
    ref = fam.reference_tree(ctx.config, initial)
    del initial
    tokens = ctx.jax.device_put(_batch(ctx, st.rows, 0), ctx.devices[0])
    want = fam.reference.loss_and_grad_norm(
        ref, tokens, micro=traffic["reference_micro_batch"],
        **fam.reference_kwargs(ctx.config))
    ok = True
    for what, got, ref_value, tol in (
            ("loss", st.first[0], want[0], traffic["loss_rel_tol"]),
            ("grad norm", st.first[1], want[1],
             traffic["grad_norm_rel_tol"])):
        rel = abs(got - ref_value) / abs(ref_value)
        ctx.log(f"train check: {what} system {got:.6f} reference "
                f"{ref_value:.6f} rel diff {rel:.2e} (tol {tol:.0e})")
        ok = ok and bool(np.isfinite(got)) and rel <= tol
    return ok
