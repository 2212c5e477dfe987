"""Benchmarks for the five reference configs (BASELINE.json):

    python bench.py                    # headline: ResNet-50, ONE JSON line
    python bench.py --model gpt2       # GPT-2 medium, tokens/s + MFU
    python bench.py --model all        # every config (headline printed last)

Each line reports throughput, step time, and TWO utilization numbers
(VERDICT r4 "what's weak" #1 — they diverge under rematerialization):

  hfu — hardware FLOPs utilization: executed TFLOP/s over peak bf16
        TFLOP/s, where executed FLOPs come from XLA's compiled-program
        cost analysis (fwd+bwd+update, FMA = 2 FLOPs). Counts remat
        RECOMPUTE, so it measures how busy the MXU is, not how much
        useful model compute it delivers.
  mfu — model FLOPs utilization: analytic, remat-invariant model FLOPs
        over the same peak. For transformer LMs the PaLM-appendix-B
        convention: 6 FLOPs per matmul parameter per token (fwd+bwd)
        plus 12·L·T·d attention FLOPs (QK^T and AV, no causal
        discount); embedding lookups are free, tied heads count once.
        For the vision configs (which run without remat) executed ==
        model FLOPs and mfu == hfu by construction.

Configs should be compared on tokens/sec and mfu; hfu explains where the
step time went (a remat config trades hfu for memory).

vs_baseline for the headline divides by 600 img/s/chip — a typical Horovod
ResNet-50/V100 fp16 figure from the reference's own benchmark suite docs.
All models run the full user path: fwd + bwd + hvd.DistributedOptimizer
update under one jit with donated state.
"""

import argparse
import json
import math
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd

BASELINE_IMG_PER_SEC = 600.0

def _peak_tflops():
    # Device peaks (and the whole r5 MFU/HFU relabel) live in exactly one
    # place now: horovod_tpu.profiler. Kept as a module function so tests
    # can monkeypatch the peak.
    from horovod_tpu import profiler
    return profiler.peak_tflops()


def _emit(rec):
    """Print one record, stamped with the device it ran on — a CPU run's
    line must never be readable as a chip number."""
    dev = jax.devices()[0]
    rec.update(platform=dev.platform, device_kind=dev.device_kind,
               devices=len(jax.devices()))
    print(json.dumps(rec), flush=True)
    return rec


def _measure(step, state, extra, steps, program="bench_step",
             model_flops=None):
    """Register the step's compiled cost analysis in the profiler's
    program registry (flops/bytes/peak-HBM — the numbers every report
    field below derives from), then time the jitted step. Returns
    ``(dt, ProgramRecord)``; the timing also feeds the live
    ``program_mfu``/``program_hfu`` gauges via ``observe_step``."""
    from horovod_tpu import profiler
    compiled = step.lower(*state, *extra).compile()
    rec = profiler.record_cost(program, compiled, model_flops=model_flops)

    # Time through the SAME compiled executable the cost came from — the
    # AOT compile doesn't populate jit's cache, so calling `step` here
    # would compile the program a second time.
    state = compiled(*state, *extra)      # warm
    state = compiled(*state, *extra)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state = compiled(*state, *extra)
    jax.block_until_ready(state)
    dt = (time.perf_counter() - t0) / steps
    profiler.observe_step(program, dt)
    return dt, rec


def _n_params(tree):
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def _lm_model_flops(n_matmul_params, n_layers, seq_len, d_attn, n_tokens):
    """Analytic model FLOPs for one fwd+bwd step over ``n_tokens`` tokens.

    PaLM Appendix-B accounting: each matmul parameter costs 2 FLOPs/token
    forward and 4 backward (6 total); attention adds 12·L·T·d_attn per
    token (QK^T + AV, forward 4·L·T·d, backward 2x). No causal discount —
    the standard convention, so numbers are comparable with public MFU
    tables. Remat-invariant by construction.
    """
    per_token = 6.0 * n_matmul_params + 12.0 * n_layers * seq_len * d_attn
    return per_token * n_tokens


def _collective_counters():
    """Collective-level observability embedded in every BENCH_*.json line:
    the active allreduce algorithm knob, negotiation round counts (full
    vs cached fast path) plus per-kind eager call/byte counters from the
    metrics registry. Cumulative over the process — diff consecutive
    lines of an `--model all` run to attribute counts to one config."""
    try:
        from horovod_tpu.collective import negotiation_stats
        from horovod_tpu.config import get_config
        from horovod_tpu.metrics import collective_summary, snapshot
        cfg = get_config()
        # Cumulative wire bytes the compiled allreduce buckets put on the
        # interconnect per ring traversal (trace-time counter, summed over
        # algorithm x wire labels) — the number the quantized formats cut.
        snap = snapshot()
        wire_bytes = sum(
            float(c.get("value", 0)) for c in
            snap.get("counters", {}).get("allreduce_wire_bytes_total", []))
        # Per-phase split of the same counter (the multi-leg 2D/swing
        # lowerings label each RS/AG leg separately; psum is phase-less).
        wire_bytes_by_phase = {}
        for c in snap.get("counters", {}).get(
                "allreduce_wire_bytes_total", []):
            ph = c.get("labels", {}).get("phase")
            if ph:
                wire_bytes_by_phase[ph] = (wire_bytes_by_phase.get(ph, 0)
                                           + int(c.get("value", 0)))
        from horovod_tpu import core as _core
        from horovod_tpu.overlap import parse_algorithm
        wire = (parse_algorithm(cfg.allreduce_algorithm)[1]
                or cfg.allreduce_wire)
        topo = (_core.topology_str() if _core.is_initialized()
                else (cfg.topology or ""))
        mesh = (_core.mesh_spec() if _core.is_initialized()
                else (cfg.mesh or ""))
        return {"allreduce_alg": cfg.allreduce_algorithm,
                "wire": wire,
                "topology": topo,
                "mesh": mesh,
                "overlap_chunks": cfg.overlap_chunks,
                "allreduce_wire_bytes": int(wire_bytes),
                "allreduce_wire_bytes_by_phase": wire_bytes_by_phase,
                "negotiation": negotiation_stats(),
                "collectives": collective_summary()}
    except Exception:
        return {}


def _report(metric, unit, per_sec, dt, flops, vs_baseline=None,
            model_flops=None, peak_hbm_bytes=None):
    """``flops`` is executed (XLA cost analysis) -> hfu; ``model_flops``
    is the analytic remat-invariant count -> mfu. When model_flops is
    None (vision configs, no remat) the two coincide. The split itself
    lives in ``profiler.utilization`` — bench only formats the line."""
    from horovod_tpu import profiler
    u = profiler.utilization(flops, dt, model_flops, peak=_peak_tflops())
    rec = {
        "metric": metric,
        "value": round(per_sec, 2),
        "unit": unit,
        "vs_baseline": (round(vs_baseline, 3) if vs_baseline is not None
                        else None),
        "step_ms": round(dt * 1e3, 2),
        "achieved_tflops": round(u["achieved_tflops"], 1),
        "model_tflops": round(u["model_tflops"], 1),
    }
    if peak_hbm_bytes is not None:
        rec["peak_hbm_bytes"] = int(peak_hbm_bytes)
    if u["hfu"] is not None:
        rec["hfu"] = round(u["hfu"], 3)
        rec["mfu"] = round(u["mfu"], 3)
    rec.update(_collective_counters())
    return _emit(rec)


def bench_resnet50(on_tpu):
    from horovod_tpu.models import ResNet50
    batch, size, steps = (128, 224, 30) if on_tpu else (8, 64, 3)
    # BN-ceiling experiments behind flags; both measured negative on the
    # chip (ROADMAP "Closed — do not retry"):
    #   HOROVOD_BENCH_BN_STATS=bf16  -> bf16 BN moment accumulation
    #   HOROVOD_BENCH_STEM=s2d       -> MLPerf space-to-depth stem
    variant = {}
    bn_stats = os.environ.get("HOROVOD_BENCH_BN_STATS", "").lower()
    if bn_stats in ("bf16", "bfloat16"):
        variant["bn_stats_dtype"] = jnp.bfloat16
    elif bn_stats in ("fp32", "float32"):
        variant["bn_stats_dtype"] = jnp.float32
    stem = os.environ.get("HOROVOD_BENCH_STEM", "").lower()
    if stem:
        variant["stem"] = stem
    model = ResNet50(num_classes=1000, **variant)
    if variant:
        print(f"# resnet50 variant: {variant}", file=sys.stderr, flush=True)
    images = jnp.asarray(
        np.random.default_rng(0).standard_normal((batch, size, size, 3)),
        jnp.bfloat16)
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, 1000, (batch,)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), images, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    opt_state = opt.init(params)

    def loss_fn(params, batch_stats, images, labels):
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
        return loss, updates["batch_stats"]

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, batch_stats, opt_state, images, labels):
        (_, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), batch_stats, opt_state

    dt, rec = _measure(step, (params, batch_stats, opt_state),
                       (images, labels), steps, program="bench:resnet50")
    return _report("resnet50_images_per_sec_per_chip", "images/sec/chip",
                   batch / dt, dt, rec.flops,
                   vs_baseline=batch / dt / BASELINE_IMG_PER_SEC,
                   peak_hbm_bytes=rec.peak_hbm_bytes)


def _bench_lm(params, tokens, loss_fn, steps, metric, model_flops=None):
    """loss_fn closes over its token batch (synthetic data is constant
    across steps); only the train state threads through the jit."""
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4))
    opt_state = opt.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        _, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    dt, rec = _measure(step, (params, opt_state), (), steps,
                       program=f"bench:{metric}", model_flops=model_flops)
    n_tokens = tokens.shape[0] * tokens.shape[1]
    return _report(metric, "tokens/sec/chip", n_tokens / dt, dt, rec.flops,
                   model_flops=rec.model_flops,
                   peak_hbm_bytes=rec.peak_hbm_bytes)


def bench_gpt2(on_tpu):
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn
    if on_tpu:
        import dataclasses
        # HOROVOD_BENCH_REMAT=full -> full block remat; the default is the
        # selective "dots" policy (save MXU outputs, recompute elementwise
        # only), measured faster than full remat on the chip in round 4
        # and fits bs8 HBM.
        cfg = dataclasses.replace(
            GPT2Config.medium(), attention="flash", remat=True,
            remat_policy=os.environ.get("HOROVOD_BENCH_REMAT", "dots"))
        B, T, steps = 8, 1024, 10
    else:
        cfg = GPT2Config.tiny()
        B, T, steps = 2, 64, 3
    model = GPT2(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, T)),
        jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    # wpe is the only lookup-only table (wte counts once: the lookup is
    # free, the tied logits matmul is not).
    mflops = _lm_model_flops(
        _n_params(params) - cfg.max_seq_len * cfg.d_model,
        cfg.num_layers, T, cfg.d_model, B * T)
    return _bench_lm(
        params, tokens,
        lambda p: loss_fn(model.apply({"params": p}, tokens), tokens),
        steps, "gpt2_medium_tokens_per_sec_per_chip", model_flops=mflops)


def bench_bert(on_tpu):
    from horovod_tpu.models.bert import Bert, BertConfig, mlm_loss
    if on_tpu:
        import dataclasses
        cfg = dataclasses.replace(
            BertConfig.large(), attention="flash", remat=True,
            remat_policy=os.environ.get("HOROVOD_BENCH_REMAT", "full"))
        B, T, steps = 8, 512, 10
    else:
        cfg = BertConfig.tiny()
        B, T, steps = 2, 64, 3
    model = Bert(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    mask_pos = jnp.asarray(rng.random((B, T)) < 0.15, jnp.float32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    def loss(p):
        mlm, _ = model.apply({"params": p}, tokens)
        return mlm_loss(mlm, tokens, mask_pos)

    # Lookup-only tables: wpe + token-type wtt (wte is tied: lookup free,
    # mlm-head matmul counted once). Bidirectional attention => full-T
    # attention FLOPs are exact here, not a convention.
    mflops = _lm_model_flops(
        _n_params(params)
        - (cfg.max_seq_len + cfg.type_vocab_size) * cfg.d_model,
        cfg.num_layers, T, cfg.d_model, B * T)
    return _bench_lm(params, tokens, loss, steps,
                     "bert_large_tokens_per_sec_per_chip",
                     model_flops=mflops)


def bench_vit(on_tpu):
    from horovod_tpu.models.vit import ViT, ViTConfig
    cfg = ViTConfig.b16() if on_tpu else ViTConfig.tiny()
    batch, steps = (128, 20) if on_tpu else (8, 3)
    model = ViT(cfg)
    size = cfg.image_size
    images = jnp.asarray(
        np.random.default_rng(0).standard_normal((batch, size, size, 3)),
        jnp.bfloat16)
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.num_classes, (batch,)),
        jnp.int32)
    params = model.init(jax.random.PRNGKey(0), images, train=True)["params"]
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4))
    opt_state = opt.init(params)

    def loss_fn(p):
        logits = model.apply({"params": p}, images, train=True)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        _, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    dt, rec = _measure(step, (params, opt_state), (), steps,
                       program="bench:vit")
    return _report("vit_b16_images_per_sec_per_chip", "images/sec/chip",
                   batch / dt, dt, rec.flops,
                   peak_hbm_bytes=rec.peak_hbm_bytes)


def bench_mnist(on_tpu):
    from horovod_tpu.models import MnistCNN
    batch, steps = (512, 30) if on_tpu else (64, 3)
    model = MnistCNN()
    images = jnp.asarray(
        np.random.default_rng(0).standard_normal((batch, 28, 28, 1)),
        jnp.float32)
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, 10, (batch,)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), images)["params"]
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    opt_state = opt.init(params)

    def loss_fn(p):
        logits = model.apply({"params": p}, images,
                             rngs={"dropout": jax.random.PRNGKey(1)})
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        _, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    dt, rec = _measure(step, (params, opt_state), (), steps,
                       program="bench:mnist")
    return _report("mnist_images_per_sec_per_chip", "images/sec/chip",
                   batch / dt, dt, rec.flops,
                   peak_hbm_bytes=rec.peak_hbm_bytes)


def _bench_torus(n):
    """Torus dims for an n-device bench ring: the HOROVOD_TOPOLOGY
    override when it factors exactly this n (the sweep shrinks n below
    the full world, where the override no longer applies), else the
    most-square factorization — the shape a real slice's detected mesh
    would approximate."""
    spec = os.environ.get("HOROVOD_TOPOLOGY")
    if spec:
        from horovod_tpu.parallel.mesh import parse_topology
        try:
            dims = parse_topology(spec)
            if int(np.prod(dims)) == n:
                return dims
        except ValueError:
            pass
    for d in range(int(math.isqrt(n)), 1, -1):
        if n % d == 0:
            return (d, n // d)
    return (n,)


def bench_allreduce(on_tpu):
    """Allreduce scaling (BASELINE's "8->256 chip scaling efficiency"
    row, measured on whatever mesh this host exposes — a virtual-CPU ICI
    proxy under the test harness, the real fabric on a multi-chip slice).

    For each device count n we time a jitted shard_map psum over the first
    n devices with a device-resident 64 MB payload and report ring bus
    bandwidth busbw = 2(n-1)/n * bytes/t; scaling efficiency is
    busbw(n) / busbw(n_min) — the fraction of per-link bandwidth kept as
    the ring grows (the metric NCCL tests report)."""
    from functools import partial as _partial

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.config import get_config
    cfg = get_config()
    alg = cfg.allreduce_algorithm

    devs = jax.devices()
    counts = [n for n in (2, 4, 8, 16, 32, 64, 128, 256)
              if n <= len(devs)]
    payload_bytes = 64 * 1024 * 1024 if on_tpu else 8 * 1024 * 1024
    per_dev = payload_bytes // 4
    steps = 20 if on_tpu else 5
    detail = {}
    busbw0 = None
    for n in counts:
        mesh = Mesh(np.asarray(devs[:n], dtype=object), ("x",))
        sharding = NamedSharding(mesh, P("x"))
        one_row = np.ones((1, per_dev), np.float32)   # one shard of host RAM
        x = jax.make_array_from_callback((n, per_dev), sharding,
                                         lambda idx: one_row)


        @jax.jit
        @_partial(jax.shard_map, mesh=mesh, in_specs=P("x"),
                  out_specs=P("x"))
        def psum_fn(v, n=n):
            # Honors HOROVOD_ALLREDUCE_ALGORITHM / --allreduce-alg, so
            # --sweep-comm measures the real per-algorithm lowering here
            # (including the quantized int8/fp8 wires and the topology-
            # aware 2D/swing schedules).
            if alg in ("psum", "auto"):
                return jax.lax.psum(v, "x")
            from horovod_tpu import overlap as _overlap
            base, qwire = _overlap.parse_algorithm(alg)
            if base == "swing":
                # every measured n is a power of two (counts above)
                return _overlap.swing_psum(v.ravel(), "x",
                                           n).reshape(v.shape)
            if base.endswith("_2d"):
                chunks = (cfg.overlap_chunks
                          if base == "chunked_rs_ag_2d" else 1)
                return _overlap.chunked_rs_ag_2d_psum(
                    v.ravel(), "x", n, dims=_bench_torus(n),
                    chunks=chunks, wire=qwire).reshape(v.shape)
            chunks = cfg.overlap_chunks if base == "chunked_rs_ag" else 1
            return _overlap.chunked_rs_ag_psum(
                v.ravel(), "x", n, chunks=chunks,
                wire=qwire).reshape(v.shape)

        psum_fn(x).block_until_ready()          # compile + warm
        t0 = time.perf_counter()
        for _ in range(steps):
            out = psum_fn(x)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / steps
        busbw = 2 * (n - 1) / n * payload_bytes / dt / 1e9
        if busbw0 is None:
            busbw0 = busbw
        detail[str(n)] = {"busbw_gbps": round(busbw, 2),
                          "efficiency": round(busbw / busbw0, 3)}
    if not counts:                              # single chip: nothing to ring
        _emit({"metric": "allreduce_scaling_efficiency", "value": 1.0,
               "unit": "fraction", "vs_baseline": None,
               "note": "single-device mesh; scaling requires >=2 devices"})
        return
    eff = detail[str(counts[-1])]["efficiency"]
    rec = {
        "metric": "allreduce_scaling_efficiency", "value": eff,
        "unit": f"fraction_busbw_{counts[0]}to{counts[-1]}dev",
        "vs_baseline": round(eff / 0.90, 3),    # BASELINE target: >=0.90
        "payload_mb": payload_bytes // (1024 * 1024),
        "detail": detail,
    }
    rec.update(_collective_counters())
    # This bench drives overlap.chunked_rs_ag_psum directly (no fused
    # allreduce buckets), so compute the per-traversal wire bytes of the
    # measured payload here instead of reading the bucket counter. The
    # bench lowering only quantizes when the ALGORITHM names a wire —
    # the config wire knob does not apply to it, so exact algorithms
    # are stamped fp32 whatever HOROVOD_ALLREDUCE_WIRE says.
    from horovod_tpu import overlap as _overlap
    base, qwire = _overlap.parse_algorithm(alg)
    wire = qwire or "fp32"
    n_max = counts[-1]
    dims = _bench_torus(n_max) if base.endswith("_2d") else None
    phases = _overlap.wire_bytes_by_phase(base, payload_bytes // 4, wire,
                                          n_max, dims=dims)
    rec["wire"] = wire
    rec["topology"] = "x".join(str(d) for d in (dims or (n_max,)))
    rec["allreduce_wire_bytes"] = sum(phases.values())
    rec["allreduce_wire_bytes_by_phase"] = phases
    return _emit(rec)


def bench_gpt2_long(on_tpu):
    """Long-context single-chip config: GPT-2 medium at 4096 tokens
    (flash + selective remat — dense attention at this length would
    materialise a 16M-score tensor per head). The long-sequence regime is
    the reference fork's north star; this is its single-chip anchor
    (multi-chip sp scales it further via ring/ulysses)."""
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn
    if on_tpu:
        import dataclasses
        cfg = dataclasses.replace(
            GPT2Config.medium(), max_seq_len=4096, attention="flash",
            remat=True,
            remat_policy=os.environ.get("HOROVOD_BENCH_REMAT", "dots"))
        B, T, steps = 2, 4096, 10
    else:
        cfg = GPT2Config.tiny()
        B, T, steps = 1, 64, 3
    model = GPT2(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, T)),
        jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    mflops = _lm_model_flops(
        _n_params(params) - cfg.max_seq_len * cfg.d_model,
        cfg.num_layers, T, cfg.d_model, B * T)
    return _bench_lm(
        params, tokens,
        lambda p: loss_fn(model.apply({"params": p}, tokens), tokens),
        steps, "gpt2_medium_4k_tokens_per_sec_per_chip",
        model_flops=mflops)


def bench_llama(on_tpu):
    """Llama-family config (GQA + RoPE + SwiGLU + RMSNorm): a ~340M
    Llama-shaped decoder at 2048 tokens, flash attention, selective remat.
    The flagship model family of the long-context fork needs its own perf
    anchor (VERDICT r4 item 2); 7B does not fit one v5e chip's HBM for
    training, so this is the largest round-number config that trains
    comfortably at B=4 (params+AdamW fp32 ~4 GB, dots-remat activations
    ~4.3 GB)."""
    from horovod_tpu.models.llama import Llama, LlamaConfig, loss_fn
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, max_seq_len=2048, num_layers=24,
            num_heads=16, num_kv_heads=4, d_model=1024, d_ff=2816,
            attention="flash", remat=True,
            remat_policy=os.environ.get("HOROVOD_BENCH_REMAT", "dots"))
        B, T, steps = 4, 2048, 10
    else:
        cfg = LlamaConfig.tiny()
        B, T, steps = 2, 64, 3
    model = Llama(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, T)),
        jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    # Only the embedding table is lookup-only (untied lm_head is a real
    # matmul). GQA expands K/V to the query head count before attention,
    # so attention FLOPs use full d_model.
    mflops = _lm_model_flops(
        _n_params(params) - cfg.vocab_size * cfg.d_model,
        cfg.num_layers, T, cfg.d_model, B * T)
    return _bench_lm(
        params, tokens,
        lambda p: loss_fn(model.apply({"params": p}, tokens), tokens),
        steps, "llama_340m_gqa_tokens_per_sec_per_chip",
        model_flops=mflops)


def bench_gpt2_packed(on_tpu):
    """Sequence-packed GPT-2 medium: the same compute shape as
    ``bench_gpt2`` but every row carries several documents with segment
    ids threading through the pallas flash kernel, packed positions, and
    the packed loss. Measures the packing-machinery tax vs plain rows —
    the number long-context users ask first."""
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config, loss_fn
    from horovod_tpu.ops.attention import packed_positions
    if on_tpu:
        import dataclasses
        cfg = dataclasses.replace(
            GPT2Config.medium(), attention="flash", remat=True,
            remat_policy=os.environ.get("HOROVOD_BENCH_REMAT", "dots"))
        B, T, steps = 8, 1024, 10
    else:
        cfg = GPT2Config.tiny()
        B, T, steps = 2, 64, 3
    model = GPT2(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    # ~4 documents per row: fixed boundaries keep shapes static and the
    # workload reproducible; real pipelines vary them per batch.
    bounds = np.sort(rng.integers(T // 8, T - T // 8, (B, 3)), axis=1)
    seg = np.zeros((B, T), np.int32)
    for b in range(B):
        for cut in bounds[b]:
            seg[b, cut:] += 1
    seg = jnp.asarray(seg)
    pos = packed_positions(seg)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    mflops = _lm_model_flops(
        _n_params(params) - cfg.max_seq_len * cfg.d_model,
        cfg.num_layers, T, cfg.d_model, B * T)
    return _bench_lm(
        params, tokens,
        lambda p: loss_fn(
            model.apply({"params": p}, tokens, segment_ids=seg,
                        positions=pos),
            tokens, segment_ids=seg),
        steps, "gpt2_medium_packed_tokens_per_sec_per_chip",
        model_flops=mflops)


def bench_t5(on_tpu):
    """T5-small-class encoder-decoder at 512/512: the zoo's third
    architecture family gets its own perf anchor (dense attention by
    construction — the per-head relative-position bias is inexpressible
    in the flash kernel's per-key fused bias)."""
    from horovod_tpu.models.t5 import (T5, T5Config, seq2seq_loss,
                                       shift_right)
    if on_tpu:
        import dataclasses
        cfg = dataclasses.replace(
            T5Config.small(), remat=True,
            remat_policy=os.environ.get("HOROVOD_BENCH_REMAT", "dots"))
        B, T, steps = 16, 512, 10
    else:
        cfg = T5Config.tiny()
        B, T, steps = 2, 32, 3
    model = T5(cfg)
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T)), jnp.int32)
    tgt = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), src,
                        shift_right(tgt, cfg.pad_id))["params"]
    # Analytic model FLOPs: all params are matmul weights except the
    # lookup-only embedding table (lm_head is untied and real);
    # attention = enc self (bidir, T_enc) + dec self (causal, T_dec) +
    # cross (T_enc keys), each 12*L*T_kv*(H*hd) per query token.
    d_attn = cfg.num_heads * cfg.head_dim
    attn = 12.0 * (cfg.num_encoder_layers * T          # enc self
                   + cfg.num_decoder_layers * T * 2)   # dec self + cross
    mflops = (6.0 * (_n_params(params)
                     - cfg.vocab_size * cfg.d_model)
              + attn * d_attn) * B * T
    return _bench_lm(
        params, tgt,
        lambda p: seq2seq_loss(model, p, src, tgt),
        steps, "t5_small_tokens_per_sec_per_chip", model_flops=mflops)


def bench_gpt2_decode(on_tpu):
    """Inference anchor: greedy KV-cache decode throughput for GPT-2
    medium (models/generate.py — one compiled lax.scan, batch 8,
    32-token prompt, 480 generated). Decode is memory-bandwidth-bound
    (every step streams the full weights for one token per row), so
    tokens/sec here tracks HBM, not the MXU — reported without
    utilization numbers by design. Throughput counts ALL scanned decode
    steps (the prompt is teacher-forced through the same cached step, at
    identical cost), so the number is per-step honest rather than
    attributing prompt steps to generated tokens."""
    from horovod_tpu.models.generate import generate
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    if on_tpu:
        cfg = GPT2Config.medium()
        B, P, N, reps = 8, 32, 480, 3
    else:
        cfg = GPT2Config.tiny()
        B, P, N, reps = 2, 4, 28, 1
    model = GPT2(cfg)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, (B, P)),
        jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    if on_tpu:
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)

    from horovod_tpu import profiler
    # The AOT compile serves BOTH the cost capture and the bench loop —
    # routing the loop through jax.jit would compile the decode scan a
    # second time (AOT compiles don't populate jit's cache).
    fn = jax.jit(lambda p, t: generate(model, p, t, N)).lower(
        params, prompt).compile()
    prec = profiler.record_cost("bench:gpt2_decode", fn)
    fn(params, prompt).block_until_ready()     # warm (already compiled)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(params, prompt)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / reps
    steps = P + N - 1                          # every scan step decodes
    # One registry "step" = one full generate() program (the compiled
    # scan), matching the cost analysis captured above.
    profiler.observe_step("bench:gpt2_decode", dt)
    rec = {
        "metric": "gpt2_medium_decode_tokens_per_sec_per_chip",
        "value": round(B * steps / dt, 2),
        "unit": "tokens/sec/chip", "vs_baseline": None,
        "step_ms": round(dt * 1e3 / steps, 3),  # per decode step
        "batch": B, "prompt": P, "new_tokens": N,
        "peak_hbm_bytes": int(prec.peak_hbm_bytes),
    }
    rec.update(_collective_counters())
    return _emit(rec)


_BENCHES = {"resnet50": bench_resnet50, "gpt2": bench_gpt2,
            "gpt2_long": bench_gpt2_long, "llama": bench_llama,
            "gpt2_packed": bench_gpt2_packed, "t5": bench_t5,
            "gpt2_decode": bench_gpt2_decode,
            "bert": bench_bert, "vit": bench_vit, "mnist": bench_mnist,
            "allreduce": bench_allreduce}


def _apply_comm_flags(args):
    """Resolve --allreduce-alg/--overlap-chunks into the HOROVOD_* env
    (read by config.refresh() inside hvd.init()), so the bench exercises
    exactly the knob surface users set."""
    if getattr(args, "allreduce_alg", None):
        os.environ["HOROVOD_ALLREDUCE_ALGORITHM"] = args.allreduce_alg
    if getattr(args, "allreduce_wire", None):
        os.environ["HOROVOD_ALLREDUCE_WIRE"] = args.allreduce_wire
    if getattr(args, "overlap_chunks", None):
        os.environ["HOROVOD_OVERLAP_CHUNKS"] = str(args.overlap_chunks)
    if getattr(args, "topology", None):
        os.environ["HOROVOD_TOPOLOGY"] = args.topology
    if getattr(args, "mesh", None):
        os.environ["HOROVOD_MESH"] = args.mesh


#: --sweep-comm measures one line per algorithm (auto is skipped: it
#: resolves to one of the explicit lowerings per bucket size). The
#: quantized wires ride the chunked pipeline — the shape they'd resolve
#: to on real gradient buckets — and the topology-aware schedules run
#: on the _bench_torus factorization of each device count.
SWEEP_ALGS = ("psum", "rs_ag", "chunked_rs_ag",
              "chunked_rs_ag_int8", "chunked_rs_ag_fp8",
              "rs_ag_2d", "chunked_rs_ag_2d", "swing")


def _load_serve_bench():
    """tools/serve_bench.py as a module (tools/ is not a package)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serve_bench.py")
    spec = importlib.util.spec_from_file_location("hvd_serve_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_serve(on_tpu):
    """--serve: Poisson-arrival serving bench (tools/serve_bench.py) —
    TTFT/TPOT/throughput percentiles under the continuous-batching
    engine. Knobs via HVD_SERVE_BENCH_{REQUESTS,RATE,SLOTS} so the CPU
    guard test stays fast without a flag zoo."""
    sb = _load_serve_bench()
    return sb.run_bench(
        model_size="medium" if on_tpu else "tiny",
        requests=int(os.environ.get(
            "HVD_SERVE_BENCH_REQUESTS", "32" if on_tpu else "10")),
        rate=float(os.environ.get("HVD_SERVE_BENCH_RATE", "25")),
        slots=int(os.environ.get(
            "HVD_SERVE_BENCH_SLOTS", "8" if on_tpu else "4")),
        max_len=256 if on_tpu else 96,
        metric="serve_tokens_per_sec_per_chip")


def _build_parser():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=list(_BENCHES) + ["all"])
    p.add_argument("--allreduce-alg", dest="allreduce_alg", default=None,
                   choices=["auto", "psum", "rs_ag", "chunked_rs_ag",
                            "rs_ag_int8", "chunked_rs_ag_int8",
                            "rs_ag_fp8", "chunked_rs_ag_fp8",
                            "rs_ag_2d", "chunked_rs_ag_2d",
                            "rs_ag_2d_int8", "chunked_rs_ag_2d_int8",
                            "rs_ag_2d_fp8", "chunked_rs_ag_2d_fp8",
                            "swing"],
                   help="gradient-sync algorithm for this run "
                        "(HOROVOD_ALLREDUCE_ALGORITHM)")
    p.add_argument("--allreduce-wire", dest="allreduce_wire", default=None,
                   choices=["fp32", "bf16", "int8", "fp8"],
                   help="default allreduce wire precision "
                        "(HOROVOD_ALLREDUCE_WIRE)")
    p.add_argument("--overlap-chunks", dest="overlap_chunks", type=int,
                   default=None,
                   help="chunked_rs_ag pipeline depth "
                        "(HOROVOD_OVERLAP_CHUNKS)")
    p.add_argument("--topology", dest="topology", default=None,
                   help="torus-dims override like 2x4 "
                        "(HOROVOD_TOPOLOGY); must factor the world size")
    p.add_argument("--mesh", dest="mesh", default=None,
                   help="dp×mp mesh like dp2xmp4 (HOROVOD_MESH); "
                        "dp*mp must equal the world size")
    p.add_argument("--sweep-comm", dest="sweep_comm", action="store_true",
                   help="one JSON line per allreduce algorithm "
                        f"({', '.join(SWEEP_ALGS)}) for the selected "
                        "model")
    p.add_argument("--serve", dest="serve", action="store_true",
                   help="Poisson-arrival serving bench (continuous-"
                        "batching engine): TTFT/TPOT/throughput "
                        "percentiles as one JSON line")
    return p


def main():
    args = _build_parser().parse_args()
    from horovod_tpu.utils import compile_cache
    compile_cache.enable()
    on_tpu = jax.default_backend() != "cpu"
    if not on_tpu and not os.environ.get(
            "JAX_PLATFORMS", "").startswith("cpu"):
        # Nobody asked for CPU, so these would be CPU numbers under the
        # chip's metric names. No chip is a failure, not a fallback.
        print("bench.py: no accelerator (jax.default_backend() == 'cpu') "
              "and JAX_PLATFORMS=cpu was not asked for; nothing was run",
              file=sys.stderr)
        return 2
    _apply_comm_flags(args)
    hvd.init()
    if args.serve:
        bench_serve(on_tpu)
        return
    if args.sweep_comm:
        # One JSON line per allreduce algorithm for the selected model
        # (headline model when "all" was asked): hvd.init() re-reads the
        # env knob, so each pass compiles and measures the real lowering.
        model = "resnet50" if args.model == "all" else args.model
        for alg in SWEEP_ALGS:
            os.environ["HOROVOD_ALLREDUCE_ALGORITHM"] = alg
            hvd.init()
            _BENCHES[model](on_tpu)
        return
    if args.model == "all":
        # headline (resnet50) last so single-line parsers read it.
        for name in ("allreduce", "mnist", "vit", "bert", "gpt2",
                     "gpt2_long", "gpt2_packed", "llama", "t5",
                     "gpt2_decode", "resnet50"):
            _BENCHES[name](on_tpu)
    else:
        _BENCHES[args.model](on_tpu)


if __name__ == "__main__":
    sys.exit(main())
