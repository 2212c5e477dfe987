#!/usr/bin/env python3
"""Bring-up smoke: does the trainer and the serving engine start on the chip?

    python chip_smoke.py            # on a machine with a TPU; fails without

One process — the only one that touches JAX — drives the system through the
entry points a user calls, at the full width of GPT-2 medium with seeded
random weights, and checks each result by the repo's own means:

1. kernels: every ``flash_attention`` variant the zoo uses, fwd+bwd, compiled
   by Mosaic (not the interpreter), against the dense reference;
2. trainer: ``hvd.init`` -> ``hvd.spmd`` -> ``hvd.value_and_grad`` +
   ``hvd.DistributedOptimizer(adamw)`` with donated state, a few steps;
3. server: ``serving.InferenceEngine`` behind ``SocketReplicaServer`` +
   ``RemoteDispatcher`` in this process, against offline ``generate()``;
4. four chips (when present): dp=4 replica agreement, the allreduce
   algorithm families on the detected torus, mp=4 serving, striped ring
   flash attention.

Any failed assertion or exception ends the run non-zero; nothing is caught
and nothing is skipped quietly. The last line of stdout is one JSON object
naming the device as JAX reports it. Every time printed here is information
about this run, not a benchmark metric.

``--rehearse-cpu`` runs the same code at toy sizes on 4 virtual CPU devices
with the kernels interpreted, to debug the script itself. It says nothing
about the chip.
"""

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time
from types import SimpleNamespace


def _sizes(rehearse: bool) -> SimpleNamespace:
    """Full-width sizes for the chip; toy sizes for the CPU rehearsal."""
    if not rehearse:
        return SimpleNamespace(
            # (name, B, T, H, D, dtype, causal, packed, key_mask, offset)
            flash=[
                ("causal d64 T1024 bf16", 2, 1024, 4, 64, "bfloat16", True, False, False, 0),
                ("causal d64 T4096 bf16", 1, 4096, 2, 64, "bfloat16", True, False, False, 0),
                ("packed d64 T1024 bf16", 2, 1024, 4, 64, "bfloat16", True, True, False, 0),
                ("bert full+key_bias d64 T512 bf16", 2, 512, 4, 64, "bfloat16", False, False, True, 0),
                ("vit ragged d64 T197 bf16", 2, 197, 4, 64, "bfloat16", False, False, False, 0),
                ("causal d128 T2048 bf16", 1, 2048, 2, 128, "bfloat16", True, False, False, 0),
                ("strict causal (offset -1) d64 T1024 bf16", 2, 1024, 4, 64, "bfloat16", True, False, False, -1),
                ("causal d64 T1024 fp32", 1, 1024, 2, 64, "float32", True, False, False, 0),
            ],
            toy_model=False, train_T=1024, batch_per_chip=8, train_steps=6,
            slots=8, max_len=1024, block=16,
            # (prompt length, new tokens); the last two share a prefix
            requests=[(5, 32), (17, 48), (17, 48), (40, 64), (130, 48),
                      (300, 64), (80, 40), (80, 40)],
            shared_prefix=64, warm_prompt=20,
            allreduce_elems=4 * 1024 * 1024, ring_T=8192, ring_H=2)
    return SimpleNamespace(
        flash=[
            ("causal d64 T128 bf16", 1, 128, 2, 64, "bfloat16", True, False, False, 0),
            ("packed d64 T128 bf16", 1, 128, 2, 64, "bfloat16", True, True, False, 0),
            ("full+key_bias d64 T64 bf16", 2, 64, 2, 64, "bfloat16", False, False, True, 0),
            ("ragged d64 T37 bf16", 1, 37, 2, 64, "bfloat16", False, False, False, 0),
            ("strict causal (offset -1) d64 T64 fp32", 1, 64, 2, 64, "float32", True, False, False, -1),
        ],
        toy_model=True, train_T=64, batch_per_chip=2, train_steps=6,
        slots=4, max_len=96, block=4,
        requests=[(3, 6), (5, 8), (5, 8), (9, 10), (20, 8), (40, 10),
                  (12, 6), (12, 6)],
        shared_prefix=8, warm_prompt=20,
        allreduce_elems=64 * 1024, ring_T=256, ring_H=2)


class _CompileCounter:
    """Programs handed to the backend, and how many the persistent cache
    answered, from jax's own monitoring events."""

    def __init__(self, jax):
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on 4 virtual CPU devices, kernels "
                         "interpreted; says nothing about the chip")
    args = ap.parse_args()
    rehearse = args.rehearse_cpu
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
        print("CPU REHEARSAL: toy sizes, interpreted kernels. This run says "
              "nothing about the chip.", flush=True)

    import jax
    import jaxlib

    import horovod_tpu as hvd
    from horovod_tpu import native
    from horovod_tpu.utils import compile_cache

    cache_from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    cache_dir = compile_cache.enable()
    compiles = _CompileCounter(jax)
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {libtpu_version}", flush=True)
    print(f"platform {device['platform']}  device_kind {device['kind']!r}  "
          f"devices {device['count']}", flush=True)
    print(f"native core: {'cpp/libhvdtpu.so' if native.native_available() else 'python fallback'}",
          flush=True)
    print(f"compile cache: {cache_dir} ("
          + ("from" if cache_from_env else "the default; nothing in")
          + " $JAX_COMPILATION_CACHE_DIR)", flush=True)
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not rehearse:
        print(f"chip_smoke: no TPU (jax.default_backend() == "
              f"{jax.default_backend()!r}); nothing was run", file=sys.stderr)
        return 2

    S = _sizes(rehearse)
    ctx = SimpleNamespace(hvd=hvd, S=S, on_chip=on_chip, compiles=compiles)
    t_start = time.perf_counter()
    for title, phase in (("1 kernels", phase_kernels),
                         ("2 trainer, one chip", phase_trainer),
                         ("3 server, one chip", phase_server),
                         ("4 four chips", phase_four_chips)):
        t0 = time.perf_counter()
        print(f"== phase {title}", flush=True)
        outcome = phase(ctx) or "ok"
        print(f"== phase {title}: {outcome} "
              f"({time.perf_counter() - t0:.1f} s, information)", flush=True)
    new = compiles.programs - compiles.cache_hits
    print(f"compile: {compiles.programs} programs, {compiles.cache_hits} "
          f"from the persistent cache, {new} compiled new, "
          f"{compiles.seconds:.1f} s in the backend; whole run "
          f"{time.perf_counter() - t_start:.1f} s (information)", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# phase 1: kernels
# ---------------------------------------------------------------------------

def _rel_err(got, want):
    """max |got - want| over max(1, max |want|), after a finiteness check."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all(), "non-finite values"
    return float(np.abs(got - want).max() / max(1.0, np.abs(want).max()))


def _flash_tol(ctx, dtype):
    """Bound on _rel_err. tests/test_flash_attention.py holds fp32 flash
    to 2e-3 of the dense path, which is the interpreter's arithmetic. bf16
    rounds at 2^-8 (4e-3), so bf16 gets 2e-2 — and so does fp32 on the
    chip, where the kernel's fp32 ``jnp.dot`` runs at the MXU's default
    precision (measured on the v5e: 3e-3 to 4e-3, the same as bf16)."""
    return 2e-3 if dtype == "float32" and not ctx.on_chip else 2e-2


def _fwd_bwd(attn, w):
    """``attn(q, k, v) -> o`` as ``(q, k, v) -> (o, dq, dk, dv)`` under the
    cotangent ``w``."""
    import jax
    import jax.numpy as jnp

    def run(q, k, v):
        def loss(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32)
                           * w.astype(jnp.float32)), o
        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (o,) + grads
    return run


def _dense_attention(q, k, v, *, causal, key_mask, seg, offset):
    """ops.attention's dense path in fp32 at full matmul precision."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.attention import multihead_attention
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        if offset:
            # visible iff q_pos + offset >= k_pos; the all-True key mask
            # turns on the dense path's "row with no visible key -> 0".
            t = q.shape[1]
            vis = jnp.arange(t)[:, None] + offset >= jnp.arange(t)[None, :]
            bias = jnp.where(vis, 0.0, -1e30)[None]
            return multihead_attention(
                q, k, v, impl="dense", causal=False, bias=bias,
                key_mask=jnp.ones(k.shape[:2], bool))
        return multihead_attention(q, k, v, impl="dense", causal=causal,
                                   key_mask=key_mask, segment_ids=seg)


def _flash_attention(q, k, v, *, causal, key_mask, seg, offset):
    from horovod_tpu.ops.attention import multihead_attention
    from horovod_tpu.ops.flash_attention import flash_attention
    if offset:
        return flash_attention(q, k, v, causal=True, causal_offset=offset)
    return multihead_attention(q, k, v, impl="flash", causal=causal,
                               key_mask=key_mask, segment_ids=seg)


def phase_kernels(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.ops.flash_attention import _use_interpret

    assert _use_interpret() is (not ctx.on_chip), \
        "flash_attention picked the Pallas interpreter on a TPU backend"
    for (name, B, T, H, D, dtype, causal, packed, masked,
         offset) in ctx.S.flash:
        rng = np.random.default_rng(0)
        q, k, v, w = (jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
                      for _ in range(4))
        seg = key_mask = None
        if packed:      # three documents per row, cut at fixed fractions
            seg = jnp.asarray(np.broadcast_to(
                np.searchsorted([T // 3, T // 2], np.arange(T),
                                side="right"), (B, T)), jnp.int32)
        if masked:      # BERT key padding: the last row is 40 % padding
            lens = np.full(B, T)
            lens[-1] = int(T * 0.6)
            key_mask = jnp.asarray(np.arange(T)[None] < lens[:, None])
        kw = dict(causal=causal, key_mask=key_mask, seg=seg, offset=offset)

        def fwd_bwd(attn):
            return jax.jit(_fwd_bwd(functools.partial(attn, **kw), w))

        lowered = fwd_bwd(_flash_attention).lower(q, k, v)
        if ctx.on_chip:
            assert "tpu_custom_call" in lowered.as_text(), \
                f"{name}: no Mosaic custom call in the lowered program"
        errs = [_rel_err(a, b) for a, b in
                zip(lowered.compile()(q, k, v),
                    fwd_bwd(_dense_attention)(q, k, v))]
        tol = _flash_tol(ctx, dtype)
        print(f"  {name}: rel err o/dq/dk/dv = "
              + " ".join(f"{e:.1e}" for e in errs) + f" (tol {tol:.0e})",
              flush=True)
        assert max(errs) <= tol, f"{name}: flash differs from dense"


# ---------------------------------------------------------------------------
# phase 2 (and 4a): the trainer
# ---------------------------------------------------------------------------

def _gpt2_medium(ctx, **kw):
    from horovod_tpu.models.gpt2 import GPT2Config
    cfg = GPT2Config.tiny() if ctx.S.toy_model else GPT2Config.medium()
    return dataclasses.replace(cfg, **kw)


def _init_params(cfg):
    """Seeded random weights. Initialised through the dense, un-remat
    twin on a short row: the parameter tree is the same and no kernel is
    compiled just to trace shapes."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.gpt2 import GPT2
    twin = GPT2(dataclasses.replace(cfg, attention="dense", remat=False))
    return jax.jit(lambda key: twin.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(0))


def _train_step(hvd, cfg):
    """The README path: ``hvd.value_and_grad`` + ``hvd.DistributedOptimizer``
    under ``hvd.spmd`` with donated state. Returns the optimizer and the
    jitted step ``(params, opt_state, tokens) -> (params, opt_state, loss)``."""
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models.gpt2 import GPT2, loss_fn
    model = GPT2(cfg)
    opt = hvd.DistributedOptimizer(optax.adamw(3e-4))

    def train_step(params, opt_state, tokens):
        def loss_of(p):
            return loss_fn(model.apply({"params": p}, tokens), tokens)
        loss, grads = hvd.value_and_grad(loss_of)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return opt, hvd.spmd(train_step, in_specs=(P(), P(), P("hvd")),
                         out_specs=(P(), P(), P()), donate_argnums=(0, 1))


def _train(ctx, n_dev):
    """A few steps on the first ``n_dev`` devices. Returns the updated
    replicated parameters for the caller's checks."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    hvd, S = ctx.hvd, ctx.S

    devs = jax.devices()[:n_dev]
    hvd.init(devices=devs)
    assert hvd.size() == n_dev
    cfg = _gpt2_medium(ctx, attention="flash", remat=True,
                       remat_policy="dots")
    batch = S.batch_per_chip * n_dev
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, S.train_T)).astype(np.int32)
    opt, step = _train_step(hvd, cfg)
    replicated = NamedSharding(hvd.mesh(), P())
    params = jax.device_put(_init_params(cfg), replicated)
    opt_state = jax.device_put(opt.init(params), replicated)
    tokens = jax.device_put(tokens, hvd.spmd_data_sharding())
    losses, times, programs = [], [], []
    for _ in range(S.train_steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready((params, opt_state, loss))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
        programs.append(ctx.compiles.programs)
    print(f"  {n_dev} device(s), global batch {batch} x {S.train_T}, "
          f"{cfg.num_layers} layers d{cfg.d_model} vocab {cfg.vocab_size}",
          flush=True)
    print("  loss per step: " + " ".join(f"{x:.4f}" for x in losses),
          flush=True)
    print(f"  first step {times[0]:.1f} s (compile included); later steps "
          + " ".join(f"{t * 1e3:.0f}" for t in times[1:])
          + " ms (information, not a benchmark)", flush=True)
    stats = devs[0].memory_stats() or {}
    print(f"  peak_bytes_in_use on {devs[0]}: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    assert all(np.isfinite(losses)), "non-finite loss"
    assert losses[-1] < losses[0], "loss did not fall on a fixed batch"
    assert step._cache_size() == 1 and programs[-1] == programs[0], \
        "the train step compiled more than once"
    # the batch is split over the axis, the parameters are whole everywhere
    assert {s.data.shape for s in tokens.addressable_shards} == \
        {(S.batch_per_chip, S.train_T)}
    for leaf in jax.tree_util.tree_leaves(params):
        assert len(leaf.addressable_shards) == n_dev and all(
            s.data.shape == leaf.shape for s in leaf.addressable_shards)
    return params


def phase_trainer(ctx):
    _train(ctx, 1)
    gc.collect()


# ---------------------------------------------------------------------------
# phase 3 (and 4c): the server
# ---------------------------------------------------------------------------

def _requests(ctx, vocab):
    """Seeded prompts; the last two share their first ``shared_prefix``
    tokens."""
    import numpy as np
    rng = np.random.default_rng(1)
    reqs = [(list(map(int, rng.integers(1, vocab, p))), n)
            for p, n in ctx.S.requests]
    head = reqs[-2][0][:ctx.S.shared_prefix]
    reqs[-1] = (head + reqs[-1][0][len(head):], reqs[-1][1])
    return reqs


def _serve(ctx, model, params, reqs, name):
    """Warm an engine, put it behind the socket server and the stream
    dispatcher in this process, send ``reqs``; returns the new tokens of
    each request."""
    import numpy as np
    from horovod_tpu.serving import (InferenceEngine, RemoteDispatcher,
                                     SocketReplicaServer)
    S = ctx.S
    eng = InferenceEngine(model, params, slots=S.slots, max_len=S.max_len,
                          block_size=S.block, prefix_cache=True, name=name)
    assert eng._donate == ((1,) if ctx.on_chip else ()), \
        "cache donation is off on the chip"
    # Warm both programs before the server's threads exist: a first
    # compile holds the GIL long enough to trip the client's breakers.
    warm = eng.submit(list(np.random.default_rng(2).integers(
        1, model.cfg.vocab_size, S.warm_prompt)), 2)
    eng.run_until_idle()
    assert warm.status.value == "done", warm.reason
    migrated = None
    if eng._mp == 1:
        # KV migration within one engine: prefill only, export the prompt's
        # K/V, graft it back, decode. With donation live these are the
        # reads and writes of the cache that happen between dispatches.
        p, n = reqs[3]
        half = eng.submit(p, n, prefill_only=True)
        eng.run_until_idle()
        assert (half.status.value, half.reason) == ("done", "prefilled")
        grafted = eng.admit_prefilled(p, n, *half.kv_export)
        eng.run_until_idle()
        assert grafted.status.value == "done", grafted.reason
        migrated = list(grafted.tokens)
    srv = SocketReplicaServer(eng, 0).start()
    try:
        disp = RemoteDispatcher([srv.address])
        t0 = time.perf_counter()
        handles = [disp.submit(p, n, deadline_s=600.0) for p, n in reqs[:-1]]
        # The prefix twin goes in once its sibling has published its
        # prompt blocks, and joins lanes that are still decoding.
        disp.wait(handles[-1])
        handles.append(disp.submit(*reqs[-1], deadline_s=600.0))
        handles = disp.wait_all(handles)
        dt = time.perf_counter() - t0
        disp.close()
    finally:
        srv.stop()
    stats = eng.stats()
    eng.close()
    for h, (p, n) in zip(handles, reqs):
        assert h.status == "done" and len(h.tokens) == n, \
            f"request with prompt {len(p)}: {h.status} {h.reason!r}"
    print(f"  {name}: {len(reqs)} requests done in {dt:.1f} s, "
          f"{stats['steps']} engine steps (information); "
          f"decode_compiles {stats['decode_compiles']}, prefill_compiles "
          f"{stats['prefill_compiles']}, prefix hits "
          f"{stats['prefix']['hits']}/{stats['prefix']['lookups']}, "
          f"kv exports/grafts {stats['kv_exports']}/{stats['kv_grafts']}, "
          f"mesh {stats['mesh']}", flush=True)
    assert eng.failed is None, eng.failed
    assert stats["decode_compiles"] == 1 and stats["prefill_compiles"] == 1
    assert stats["prefix"]["hits"] > 0, "the shared prefix was not reused"
    tokens = [list(h.tokens) for h in handles]
    assert migrated in (None, tokens[3]), \
        "a request decoded from migrated K/V differs from the served one"
    return tokens


@functools.lru_cache(maxsize=None)
def _offline_scorer(cfg):
    """``(params, seq_a, seq_b) -> (top, logit_a, logit_b)``: teacher-force
    ``seq_a`` through the offline decode step — the one ``generate()``
    scans — and score, at every position, the next token of ``seq_a`` and
    of ``seq_b`` against the top logit. Three ``(B, L-1)`` arrays: column
    t judges the token at position t+1. One jitted function per config,
    so the four-chip phase reuses the one-chip phase's program."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.generate import decode_step, init_cache
    step = decode_step(cfg)

    @jax.jit
    def run(params, seq_a, seq_b):
        B, L = seq_a.shape

        def body(cache, t):
            cache, logits = step(params, cache, seq_a[:, t], t)
            pick = lambda s: jnp.take_along_axis(
                logits, s[:, t + 1][:, None], axis=1)[:, 0]
            return cache, (logits.max(axis=-1), pick(seq_a), pick(seq_b))

        _, out = jax.lax.scan(body, init_cache(cfg, B, L),
                              jnp.arange(L - 1))
        return tuple(x.T for x in out)

    return run


def _assert_agree(ctx, cfg, params, reqs, got, other, what):
    """``got`` (the engine's tokens) must equal ``other`` token for token,
    or part from it only at a near-tie: bf16 lowerings of one model differ
    by an ulp or two in the logits (tests/test_generate.py pins bf16
    decode at the logit level for that reason), and with random weights
    the top two logits are often that close. A parting counts as agreement
    when both candidates sit within ``0.02 * max(1, |top|)`` of the top
    offline logit — the tests' atol, scaled like ``greedy_token``'s band.
    Every token the engine chose is held to the same band."""
    import numpy as np
    L = max(len(p) + n for p, n in reqs)
    seq_a = np.zeros((len(reqs), L), np.int32)
    seq_b = np.zeros((len(reqs), L), np.int32)
    for i, ((p, _), a, b) in enumerate(zip(reqs, got, other)):
        seq_a[i, :len(p) + len(a)] = p + a
        seq_b[i, :len(p) + len(b)] = p + b
    top, la, lb = (np.asarray(x) for x in
                   _offline_scorer(cfg)(params, seq_a, seq_b))
    band = 0.02 * np.maximum(1.0, np.abs(top))
    exact, worst = 0, 0.0
    for i, ((p, n), a, b) in enumerate(zip(reqs, got, other)):
        cols = slice(len(p) - 1, len(p) - 1 + n)
        short = (top - la)[i, cols]
        worst = max(worst, float((short / band[i, cols]).max()))
        assert (short <= band[i, cols]).all(), \
            f"{what}: request {i} chose a token below the offline top " \
            f"logit by {short.max():.3f}"
        if a == b:
            exact += 1
            continue
        j = next(t for t in range(n) if a[t] != b[t])
        c = len(p) - 1 + j
        assert top[i, c] - lb[i, c] <= band[i, c], \
            f"{what}: request {i} parts at token {j} and it is no " \
            f"near-tie ({top[i, c] - lb[i, c]:.3f} below the top logit)"
    print(f"  {what}: {exact}/{len(reqs)} requests token-identical, "
          f"{len(reqs) - exact} part at a near-tie; the engine's worst "
          f"token sits at {worst:.2f} of the band below the top logit",
          flush=True)


def phase_server(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.models.generate import generate
    from horovod_tpu.models.gpt2 import GPT2

    cfg = _gpt2_medium(ctx)
    model = GPT2(cfg)
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                    _init_params(cfg))
    reqs = _requests(ctx, cfg.vocab_size)
    got = _serve(ctx, model, params, reqs, "smoke-mp1")
    offline = [None] * len(reqs)
    for shape in sorted({(len(p), n) for p, n in reqs}):
        rows = [i for i, (p, n) in enumerate(reqs) if (len(p), n) == shape]
        out = np.asarray(generate(
            model, params, jnp.asarray([reqs[i][0] for i in rows],
                                       jnp.int32), shape[1]))
        for i, row in zip(rows, out):
            offline[i] = list(map(int, row[shape[0]:]))
    _assert_agree(ctx, cfg, params, reqs, got, offline,
                  "engine vs offline generate()")
    ctx.served = SimpleNamespace(cfg=cfg, model=model, params=params,
                                 reqs=reqs, tokens=got)


# ---------------------------------------------------------------------------
# phase 4: four chips
# ---------------------------------------------------------------------------

def phase_four_chips(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    hvd, S = ctx.hvd, ctx.S
    n = len(jax.devices())
    if n < 4:
        return f"not run: {n} device(s)"

    # (a) the trainer at dp=4: every replica must hold the same update.
    # hvd.spmd runs with check_vma=False, so a missing reduction would
    # hand back device 0's values and look fine from the host.
    params = _train(ctx, 4)

    def spread(params):
        total = sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                    for x in jax.tree_util.tree_leaves(params))
        return (hvd.allreduce(total, op=hvd.Max)
                - hvd.allreduce(total, op=hvd.Min)), total

    gap, total = hvd.spmd(spread, in_specs=(P(),),
                          out_specs=(P(), P()))(params)
    print(f"  (a) parameter checksum {float(total):.6e}, max - min over "
          f"the hvd axis = {float(gap)}", flush=True)
    assert float(gap) == 0.0, "replicas hold different parameters"
    del params
    gc.collect()

    # (b) one eager allreduce per algorithm family against psum.
    hvd.init(devices=jax.devices()[:4])
    print(f"  (b) hvd.topology() = {hvd.topology()}", flush=True)
    if ctx.on_chip:
        assert hvd.topology() == (2, 2), "the 2x2 torus was not detected"
    x = np.random.default_rng(3).standard_normal(
        (4, S.allreduce_elems)).astype(np.float32)
    want = np.asarray(hvd.allreduce(x, op=hvd.Sum, algorithm="psum"))
    np.testing.assert_allclose(want[0], x.sum(0), rtol=1e-5, atol=1e-5)
    for alg in ("chunked_rs_ag", "rs_ag_2d", "swing", "rs_ag_int8"):
        got = np.asarray(hvd.allreduce(x, op=hvd.Sum, algorithm=alg))
        err = float(np.abs(got - want).max())
        # exact wires differ from psum only in summation order; the int8
        # wire rounds each of the two legs to 1/127 of a block's maximum
        tol = 2.5 * np.abs(want).max() / 127 if alg.endswith("int8") \
            else 1e-4
        print(f"      {alg}: max |x - psum| = {err:.2e} (tol {tol:.2e})",
              flush=True)
        assert err <= tol and (got == got[0]).all(), \
            f"{alg} disagrees with psum or between ranks"

    # (c) the server under HOROVOD_MESH=dp1xmp4 against the mp=1 engine.
    os.environ["HOROVOD_MESH"] = "dp1xmp4"
    try:
        hvd.init(devices=jax.devices()[:4])
        assert hvd.mp_size() == 4
        sv = ctx.served
        got = _serve(ctx, sv.model, sv.params, sv.reqs, "smoke-mp4")
    finally:
        del os.environ["HOROVOD_MESH"]
    _assert_agree(ctx, sv.cfg, sv.params, sv.reqs, got, sv.tokens,
                  "dp1xmp4 engine vs mp=1 engine")
    hvd.init(devices=jax.devices()[:4])

    # (d) striped ring flash attention over sp=4, fwd+bwd.
    from horovod_tpu.ops.ring_flash import ring_flash_attention
    T, H, D = S.ring_T, S.ring_H, 64
    rng = np.random.default_rng(4)
    q, k, v, w = (jnp.asarray(rng.standard_normal((1, T, H, D)),
                              jnp.bfloat16) for _ in range(4))
    stripe = lambda a: jnp.concatenate([a[:, r::4] for r in range(4)], 1)

    def ring(q, k, v, w):
        return _fwd_bwd(functools.partial(
            ring_flash_attention, axis_name="hvd", causal=True,
            layout="striped"), w)(q, k, v)

    seq = P(None, "hvd")
    lowered = hvd.spmd(ring, in_specs=(seq,) * 4,
                       out_specs=(seq,) * 4).lower(*map(stripe, (q, k, v, w)))
    if ctx.on_chip:
        assert "tpu_custom_call" in lowered.as_text()
    got = lowered.compile()(*map(stripe, (q, k, v, w)))

    want = jax.jit(_fwd_bwd(functools.partial(
        _dense_attention, causal=True, key_mask=None, seg=None, offset=0),
        w))(q, k, v)
    errs = [_rel_err(a, stripe(b)) for a, b in zip(got, want)]
    print(f"  (d) striped ring flash sp=4 T{T}: rel err o/dq/dk/dv = "
          + " ".join(f"{e:.1e}" for e in errs), flush=True)
    assert max(errs) <= _flash_tol(ctx, "bfloat16")


if __name__ == "__main__":
    sys.exit(main())
