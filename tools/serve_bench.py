#!/usr/bin/env python
"""Serving load generator: Poisson arrivals against one InferenceEngine,
TTFT / TPOT / throughput percentiles as JSON lines.

A decode program's raw token rate is not what users feel: that is time
to *first* token under contention (TTFT), steady-state time per output
token (TPOT), and how both degrade as the arrival rate climbs. This
tool measures exactly that: requests arrive on a seeded exponential
clock, prompt lengths and output budgets drawn from seeded ranges, the
engine serves them under its real continuous-batching scheduler, and
the record carries p50/p90/p99 of every latency plus goodput.

One JSON line per run to stdout (append with ``--out``).

Usage::

    python tools/serve_bench.py                 # tiny model, CPU
    python tools/serve_bench.py --requests 64 --rate 20 --slots 8
    python tools/serve_bench.py --kv-quant int8 --prefill-chunk 16
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _pct(xs, q):
    if not xs:
        return None
    xs = sorted(xs)
    i = min(len(xs) - 1, max(0, int(round(q / 100 * (len(xs) - 1)))))
    return round(xs[i], 6)


def _summary(xs):
    return {"p50": _pct(xs, 50), "p90": _pct(xs, 90),
            "p99": _pct(xs, 99), "n": len(xs)}


def run_bench(*, requests: int = 32, rate: float = 50.0,
              slots: int = 8, max_len: int = 160,
              block_size: int = 16, prefill_chunk: int = 8,
              kv_quant=None, num_blocks=None,
              model_size: str = "tiny", seed: int = 0,
              transport: str = "none",
              prefix_overlap: float = 0.0, prefix_cache: bool = False,
              spec_k: int = 0,
              metric: str = "serve_tokens_per_sec") -> dict:
    """Run one load level; returns (and prints) the record.

    ``transport`` selects the path between the load generator and the
    engine: ``none`` (direct ``engine.submit``, the PR 4 baseline),
    ``spool`` (the filesystem replica protocol), ``socket`` (legacy
    one-shot JSON-over-TCP through a ``RemoteDispatcher``), or
    ``stream`` (the v2 persistent multiplexed wire with server-push
    tokens) — same Poisson load, so the lines are comparable and the
    delta IS the transport's latency cost. Socket/stream rows also
    record ``ttft_client_s``: first-token latency as the CLIENT sees
    it, which is where the legacy poll interval shows up and the v2
    push removes it.

    ``prefix_overlap=R`` makes fraction R of the requests share one
    long preamble (4 blocks of tokens) ahead of their individual tails
    — the chat/system-prompt workload shape prefix caching exists for.
    Same seeded arrivals and tails whatever ``prefix_cache`` says, so
    an off/on pair differs ONLY in the cache knob and the TTFT delta is
    the cache's doing."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    from horovod_tpu.serving import reqtrace
    from horovod_tpu.serving.engine import InferenceEngine

    # With HOROVOD_REQUEST_TRACE=1 every benched request is span-traced
    # and the record carries the mean TTFT component breakdown (and
    # ``request_trace``, so a traced row is never read beside an
    # untraced one).
    trace_on = reqtrace.enabled()
    if trace_on:
        reqtrace.reset()

    if model_size == "tiny":
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        max_len = min(max_len, cfg.max_seq_len)
    else:
        # gpt2-medium geometry.
        cfg = GPT2Config(vocab_size=50257, max_seq_len=max(max_len, 1024),
                         num_layers=24, num_heads=16, d_model=1024,
                         dtype=jnp.bfloat16)
    model = GPT2(cfg)
    rng = np.random.default_rng(seed)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, 8), jnp.int32))["params"]

    eng = InferenceEngine(model, params, slots=slots, max_len=max_len,
                          block_size=block_size,
                          prefill_chunk=prefill_chunk,
                          kv_quant=kv_quant, num_blocks=num_blocks,
                          queue_limit=max(64, 4 * requests),
                          prefix_cache=prefix_cache, spec_k=spec_k,
                          name="serve-bench")
    eng.start()

    # Warm both programs outside the measured window, so the record
    # reports serving latency, not compile latency.
    warm = eng.submit([1, 2, 3, 4, 5], 4)
    warm.result(timeout=600)

    srv = None
    disp = None
    root = None
    if transport == "spool":
        import tempfile
        from horovod_tpu.serving.replica import ReplicaServer
        root = tempfile.mkdtemp(prefix="hvd_serve_bench_spool_")
        srv = ReplicaServer(root, 0, eng, heartbeat_s=0.5).start()
    elif transport in ("socket", "stream"):
        from horovod_tpu.serving.transport import (
            RemoteClient, RemoteDispatcher, SocketReplicaServer)
        srv = SocketReplicaServer(eng, 0).start()
        # Pin the wire explicitly: "socket" means the legacy one-shot
        # JSON protocol even when the config default is stream, so the
        # socket-vs-stream rows measure the wire, not the default knob.
        wire = "legacy" if transport == "socket" else "stream"
        disp = RemoteDispatcher(
            clients=[RemoteClient(srv.address, transport=wire)])
    elif transport != "none":
        raise ValueError(f"unknown transport {transport!r}")

    gaps = rng.exponential(1.0 / rate, size=requests)
    # Shared preamble: 4 whole blocks, shrunk if max_len can't fit
    # preamble + tail + budget. Only drawn when overlap is requested, so
    # the prompt stream at overlap 0 is byte-identical to older runs.
    if prefix_overlap > 0:
        pre_len = min(4 * block_size,
                      max(0, (max_len - 16 - 32) // block_size) * block_size)
        preamble = [int(t) for t in
                    rng.integers(1, cfg.vocab_size - 1, pre_len)]
        shared = rng.random(requests) < prefix_overlap
    else:
        preamble, shared = [], np.zeros(requests, bool)
    prompts = []
    for i in range(requests):
        tail = [int(t) for t in rng.integers(1, cfg.vocab_size - 1,
                                             int(rng.integers(4, 17)))]
        prompts.append(preamble + tail if shared[i] else tail)
    budgets = [int(rng.integers(8, 33)) for _ in range(requests)]

    # outs: one dict per request with the SAME keys whatever the path,
    # so the percentile summaries below don't care which transport ran.
    outs = []
    t0 = time.perf_counter()
    if transport == "none":
        reqs = []
        for gap, p, n in zip(gaps, prompts, budgets):
            time.sleep(float(gap))
            tr = ({"trace": reqtrace.mint_context().wire()}
                  if trace_on else {})
            reqs.append(eng.submit(p, n, **tr))
        for r in reqs:
            try:
                r.result(timeout=600)
            except TimeoutError:
                pass
        outs = [{"status": r.status.value, "tokens": len(r.tokens),
                 "ttft": r.ttft, "tpot": r.tpot,
                 "queue_wait": r.queue_wait} for r in reqs]
    elif transport == "spool":
        from horovod_tpu.serving.replica import (
            submit_file_request, wait_file_result)
        ids = []
        for i, (gap, p, n) in enumerate(zip(gaps, prompts, budgets)):
            time.sleep(float(gap))
            ids.append(submit_file_request(root, p, n,
                                           request_id=f"bench-{i}"))
        for rid in ids:
            try:
                r = wait_file_result(root, rid, timeout=600)
            except TimeoutError:
                outs.append({"status": "timeout", "tokens": 0,
                             "ttft": None, "tpot": None,
                             "queue_wait": None})
                continue
            outs.append({"status": r["status"],
                         "tokens": len(r["tokens"]),
                         "ttft": r.get("ttft"), "tpot": r.get("tpot"),
                         "queue_wait": r.get("queue_wait")})
    else:
        handles = []
        for gap, p, n in zip(gaps, prompts, budgets):
            time.sleep(float(gap))
            handles.append(disp.submit(p, n))
        for h in handles:
            disp.wait(h, timeout=600)
            outs.append({"status": h.status, "tokens": len(h.tokens),
                         "ttft": h.ttft, "tpot": h.tpot,
                         "ttft_client": h.ttft_client,
                         "queue_wait": None})
        disp.close()
    wall = time.perf_counter() - t0
    if srv is not None:
        srv.stop()                      # stops the engine too
    else:
        eng.stop()

    done = [o for o in outs if o["status"] == "done"]
    tokens = sum(o["tokens"] for o in outs)
    pstats = eng.manager.prefix_stats()
    estats = eng.stats()
    ttfts = [o["ttft"] for o in done if o["ttft"] is not None]
    import jax
    dev = jax.devices()[0]
    rec = {
        "metric": metric,
        "value": round(tokens / wall, 2),
        "unit": "tokens/sec", "vs_baseline": None,
        # proxy: a CPU run, which says what the engine counts and never
        # how fast it is. The device fields say where any row ran.
        "proxy": dev.platform == "cpu",
        "platform": dev.platform, "device_kind": dev.device_kind,
        "devices": len(jax.devices()),
        "transport": transport,
        "requests": requests, "completed": len(done),
        "rejected": sum(1 for o in outs
                        if o["status"] == "rejected"),
        "arrival_rate_hz": rate, "wall_s": round(wall, 3),
        "slots": slots, "max_len": max_len, "block_size": block_size,
        "prefill_chunk": prefill_chunk, "kv_quant": kv_quant,
        "model": f"gpt2-{model_size}",
        "mesh": estats.get("mesh") or "",
        "mp": estats.get("mp", 1),
        "param_bytes_per_rank": estats.get("param_bytes_per_rank"),
        "prefix_overlap": prefix_overlap, "prefix_cache": prefix_cache,
        "spec_k": spec_k,
        "prefix_hit_rate": round(pstats["hit_rate"], 4),
        "prefix_tokens_reused": pstats["tokens_reused"],
        "spec_proposed": estats["spec_proposed"],
        "spec_accepted": estats["spec_accepted"],
        "ttft_mean_s": (round(sum(ttfts) / len(ttfts), 6)
                        if ttfts else None),
        "ttft_s": _summary(ttfts),
        "ttft_client_s": _summary([o["ttft_client"] for o in done
                                   if o.get("ttft_client") is not None]),
        "tpot_s": _summary([o["tpot"] for o in done
                            if o["tpot"] is not None]),
        "queue_wait_s": _summary([o["queue_wait"] for o in done
                                  if o["queue_wait"] is not None]),
        "blocks_peak": eng.manager.peak_blocks_in_use,
        "blocks_capacity": eng.manager.capacity,
        "dense_equivalent_blocks": slots * eng.max_blocks_per_slot,
        "decode_compiles": eng.decode_compiles,
        "prefill_compiles": eng.prefill_compiles,
        "request_trace": trace_on,
    }
    # SLO summary: compare the run's observed TTFT p99 / error fraction
    # against the targets the fleet health plane alerts on
    # (HOROVOD_SLO_TTFT_P99_MS / HOROVOD_SLO_ERROR_RATE), so a bench line
    # records pass/fail against the same budgets the continuous doctor
    # burns against.
    from horovod_tpu import config as _hvd_config
    _cfg = _hvd_config.get_config()
    ttft_sum = rec["ttft_s"] or {}
    obs_ttft_p99_ms = (round(ttft_sum["p99"] * 1000.0, 3)
                       if ttft_sum.get("p99") is not None else None)
    errors = sum(1 for o in outs
                 if o["status"] in ("rejected", "expired", "failed"))
    obs_err = round(errors / max(1, len(outs)), 4)
    rec["slo_ttft_p99_ms"] = _cfg.slo_ttft_p99_ms
    rec["slo_error_rate"] = _cfg.slo_error_rate
    rec["slo"] = {
        "ttft_p99_ms_target": _cfg.slo_ttft_p99_ms or None,
        "ttft_p99_ms": obs_ttft_p99_ms,
        "ttft_ok": (None if not _cfg.slo_ttft_p99_ms
                    or obs_ttft_p99_ms is None
                    else obs_ttft_p99_ms <= _cfg.slo_ttft_p99_ms),
        "error_rate_target": _cfg.slo_error_rate or None,
        "error_rate": obs_err,
        "errors_ok": (None if not _cfg.slo_error_rate
                      else obs_err <= _cfg.slo_error_rate),
    }
    if trace_on:
        from horovod_tpu.trace_merge import request_report
        mean = request_report(
            reqtrace.events()).get("breakdown_mean_s") or {}
        for comp in ("queue", "prefill", "decode", "push"):
            rec[f"breakdown_{comp}_s"] = round(mean.get(comp, 0.0), 6)
    print(json.dumps(rec), flush=True)
    return rec


def run_storm_bench(*, roles: str = "1x2", requests: int = 32,
                    rate: float = 30.0, slots: int = 2,
                    max_len: int = 160, block_size: int = 16,
                    prefill_chunk: int = 8, kv_quant=None,
                    wire: str = "", seed: int = 0,
                    prefix_overlap: float = 0.6,
                    affinity: bool = True) -> list:
    """Prefill-storm comparison: the SAME seeded workload served by a
    monolithic pool of P+D ``both`` engines and by a disaggregated
    P-prefill/D-decode split (``roles="PxD"``), in-process via
    :func:`horovod_tpu.serving.disagg.migrate_local` — the full wire
    codec minus the socket.

    The workload is the shape disaggregation exists for: roughly half
    the arrivals are "storm" requests (a long shared preamble + tail,
    tiny decode budget — pure prefill pressure), interleaved with chat
    requests (short prompt, long decode). Monolithically, every chunked
    prefill steals decode steps from in-flight chats, showing up as
    TPOT tail latency; split, the decode pool never runs a prefill and
    the storm only costs the chats their migration hop.

    Emits three records: one per mode (``serve_storm_tokens_per_sec``
    with TTFT/TPOT percentile summaries, distinguished by the
    ``serve_role`` settings field) plus a
    mono-over-disagg p99-TPOT ratio line (higher is better; >= 1.0
    means the decode tail was no worse under disaggregation). The
    disagg record also carries the prefix-cache hit rates: ``local``
    (what the prefill engines actually observed) vs ``fleet`` (the
    oracle rate a single fleet-wide cache would have seen) — with
    affinity routing on, local ~= fleet is the whole point.
    """
    import threading

    import numpy as np
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    from horovod_tpu.serving import disagg
    from horovod_tpu.serving.engine import InferenceEngine

    try:
        n_pre, n_dec = (int(x) for x in roles.lower().split("x"))
    except ValueError:
        raise ValueError(f"--roles must look like PxD, got {roles!r}")
    if n_pre < 1 or n_dec < 1:
        raise ValueError(f"--roles needs at least 1x1, got {roles!r}")

    cfg = GPT2Config.tiny(dtype=jnp.float32)
    max_len = min(max_len, cfg.max_seq_len)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(seed)

    # The seeded workload, fixed across both modes. Storm prompts share
    # one of a few long preambles (prefix_overlap of them), so the
    # prefix cache has something to reuse and affinity routing has
    # something to concentrate.
    pre_len = min(3 * block_size,
                  max(1, (max_len - 16 - 32) // block_size) * block_size)
    preambles = [[int(t) for t in
                  rng.integers(1, cfg.vocab_size - 1, pre_len)]
                 for _ in range(3)]
    gaps = rng.exponential(1.0 / rate, size=requests)
    work = []
    for i in range(requests):
        tail = [int(t) for t in rng.integers(1, cfg.vocab_size - 1,
                                             int(rng.integers(4, 10)))]
        if rng.random() < 0.5:         # storm: long prompt, short decode
            if rng.random() < prefix_overlap:
                prompt = preambles[int(rng.integers(len(preambles)))] \
                    + tail
            else:
                prompt = [int(t) for t in
                          rng.integers(1, cfg.vocab_size - 1,
                                       pre_len)] + tail
            budget = int(rng.integers(4, 9))
            kind = "storm"
        else:                          # chat: short prompt, long decode
            prompt = tail
            budget = int(rng.integers(16, 25))
            kind = "chat"
        work.append((float(gaps[i]), prompt, budget,
                     disagg.prefix_fingerprint(prompt), kind))

    # Oracle fleet hit rate: the rate ONE fleet-wide cache would see —
    # every arrival whose fingerprint any earlier arrival already
    # carried. Affinity routing exists to make the observed local rate
    # approach this.
    seen = set()
    fleet_hits = 0
    for _, prompt, _, fp, _ in work:
        if len(prompt) >= block_size:
            if fp in seen:
                fleet_hits += 1
            seen.add(fp)
    fleet_rate = round(fleet_hits / max(1, len(work)), 4)

    def _mk_engine(role, name):
        eng = InferenceEngine(
            model, params, slots=slots, max_len=max_len,
            block_size=block_size, prefill_chunk=prefill_chunk,
            kv_quant=kv_quant, queue_limit=max(64, 4 * requests),
            prefix_cache=True, role=role, name=name)
        eng.start()
        warm = eng.submit([1, 2, 3, 4, 5], 4,
                          prefill_only=(role == "prefill"))
        warm.result(timeout=600)
        return eng

    def _route(engines, fp):
        if affinity and fp is not None:
            by_name = {e.name: e for e in engines}
            order = disagg.rank_by_affinity(fp, sorted(by_name))
            return by_name[order[0]]
        return min(engines, key=lambda e: e.load())

    def _drive(mode):
        if mode == "mono":
            pool = [_mk_engine("both", f"mono{i}")
                    for i in range(n_pre + n_dec)]
            pre_pool, dec_pool = pool, pool
        else:
            pre_pool = [_mk_engine("prefill", f"pre{i}")
                        for i in range(n_pre)]
            dec_pool = [_mk_engine("decode", f"dec{i}")
                        for i in range(n_dec)]
            pool = pre_pool + dec_pool
        outs = [None] * len(work)
        threads = []

        def _serve_one(i, prompt, budget, fp, kind, t_arr):
            try:
                if mode == "mono":
                    r = _route(pre_pool, fp).submit(list(prompt), budget)
                    r.result(timeout=600)
                else:
                    r1 = _route(pre_pool, fp).submit(
                        list(prompt), budget, prefill_only=True)
                    r1.result(timeout=600)
                    if r1.status.value != "done":
                        outs[i] = {"status": r1.status.value,
                                   "tokens": 0, "ttft": None,
                                   "tpot": None}
                        return
                    # Pool pressure rejects the graft retryable — spin
                    # on the least-loaded decode engine until a slot
                    # frees, the in-process analogue of the
                    # dispatcher's re-place loop.
                    give_up = time.monotonic() + 600
                    while True:
                        dst = min(dec_pool, key=lambda e: e.load())
                        r = disagg.migrate_local(r1, dst, wire=wire)
                        if r.status.value != "rejected" \
                                or time.monotonic() >= give_up:
                            break
                        time.sleep(0.005)
                    r.result(timeout=600)
                outs[i] = {
                    "status": r.status.value, "tokens": len(r.tokens),
                    "kind": kind,
                    "ttft": (r.t_first - t_arr
                             if r.t_first is not None else None),
                    "tpot": r.tpot}
            except Exception as e:          # noqa: BLE001 - record it
                outs[i] = {"status": f"error: {e}", "tokens": 0,
                           "kind": kind, "ttft": None, "tpot": None}

        t0 = time.perf_counter()
        for i, (gap, prompt, budget, fp, kind) in enumerate(work):
            time.sleep(gap)
            t = threading.Thread(
                target=_serve_one,
                args=(i, prompt, budget, fp, kind, time.monotonic()),
                daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=600)
        wall = time.perf_counter() - t0

        pstats = [e.manager.prefix_stats() for e in pre_pool]
        lookups = sum(p["lookups"] for p in pstats)
        hits = sum(p["hits"] for p in pstats)
        for e in pool:
            e.stop()
        done = [o for o in outs if o and o["status"] == "done"]
        ttfts = [o["ttft"] for o in done if o["ttft"] is not None]
        # The storm's victim metric is the CHAT decode tail: storm
        # requests barely decode (tiny budgets), so folding them in
        # would dilute exactly the interleave tax disaggregation
        # removes. tpot_s is chats-only; tpot_all_s keeps everything.
        tpots = [o["tpot"] for o in done
                 if o["tpot"] is not None and o["kind"] == "chat"]
        tpots_all = [o["tpot"] for o in done if o["tpot"] is not None]
        return {
            "metric": "serve_storm_tokens_per_sec",
            "value": round(sum(o["tokens"] for o in done) / wall, 2),
            "unit": "tokens/sec", "vs_baseline": None, "proxy": True,
            "transport": "none",
            "serve_role": ("both" if mode == "mono"
                           else f"{n_pre}x{n_dec}"),
            "kv_wire": ("" if mode == "mono" else
                        (wire or disagg.default_wire(kv_quant,
                                                     cfg.dtype))),
            "requests": requests, "completed": len(done),
            "arrival_rate_hz": rate, "wall_s": round(wall, 3),
            "slots": slots, "max_len": max_len,
            "block_size": block_size, "prefill_chunk": prefill_chunk,
            "kv_quant": kv_quant, "model": "gpt2-tiny",
            "prefix_overlap": prefix_overlap, "prefix_cache": True,
            "affinity": affinity,
            "prefix_hit_rate_local": round(hits / max(1, lookups), 4),
            "prefix_hit_rate_fleet": fleet_rate,
            "ttft_s": _summary(ttfts),
            "tpot_s": _summary(tpots),
            "tpot_all_s": _summary(tpots_all),
        }

    mono = _drive("mono")
    split = _drive("disagg")
    recs = [mono, split]
    mono_p99 = (mono["tpot_s"] or {}).get("p99")
    split_p99 = (split["tpot_s"] or {}).get("p99")
    if mono_p99 and split_p99:
        recs.append({
            "metric": "serve_storm_tpot_mono_over_disagg",
            "value": round(mono_p99 / split_p99, 4), "unit": "x",
            "vs_baseline": None, "proxy": True,
            "serve_role": f"{n_pre}x{n_dec}",
            "kv_wire": split["kv_wire"], "requests": requests,
            "arrival_rate_hz": rate, "slots": slots,
            "max_len": max_len, "block_size": block_size,
            "prefill_chunk": prefill_chunk, "kv_quant": kv_quant,
            "model": "gpt2-tiny", "prefix_overlap": prefix_overlap,
            "affinity": affinity,
            "tpot_p99_mono_s": mono_p99,
            "tpot_p99_disagg_s": split_p99,
        })
    for r in recs:
        print(json.dumps(r), flush=True)
    return recs


def _build_parser():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate", type=float, default=50.0,
                   help="Poisson arrival rate (requests/sec)")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--max-len", type=int, default=160)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--prefill-chunk", type=int, default=8)
    p.add_argument("--kv-quant", choices=["int8", "fp8"], default=None)
    p.add_argument("--num-blocks", type=int, default=None,
                   help="shared KV pool size (default: dense equivalent)")
    p.add_argument("--model-size", choices=["tiny", "medium"],
                   default="tiny")
    p.add_argument("--transport",
                   choices=["none", "spool", "socket", "stream"],
                   default="none",
                   help="path between load generator and engine: direct "
                   "submit, filesystem spool, legacy socket RPC, or the "
                   "v2 multiplexed push stream")
    p.add_argument("--prefix-overlap", type=float, default=0.0,
                   help="fraction of requests sharing a 4-block preamble")
    p.add_argument("--prefix-cache", action="store_true",
                   help="enable the shared-prefix KV cache in the engine")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative drafts per decode step (0 = off)")
    p.add_argument("--prefix-compare", action="store_true",
                   help="run the same workload with prefix cache off then "
                   "on and append gated hit-rate / TTFT-speedup lines")
    p.add_argument("--prefill-storm", action="store_true",
                   help="run the prefill-storm workload monolithically "
                   "AND disaggregated (--roles) and append comparable "
                   "TTFT/TPOT lines plus a p99-TPOT ratio line")
    p.add_argument("--roles", default="1x2",
                   help="disaggregated pool shape PxD for "
                   "--prefill-storm (default 1x2: one prefill, two "
                   "decode replicas)")
    p.add_argument("--kv-wire", default="",
                   choices=["", "fp32", "bf16", "int8", "fp8"],
                   help="KV migration wire format for --prefill-storm "
                   "(default: engine dtype/quant decides)")
    p.add_argument("--no-affinity", action="store_true",
                   help="scatter requests least-loaded instead of "
                   "routing by prompt-prefix fingerprint "
                   "(--prefill-storm only)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None,
                   help="append the JSON record to this file")
    return p


def main() -> int:
    args = _build_parser().parse_args()
    from horovod_tpu.utils import compile_cache
    compile_cache.enable()
    kw = dict(
        requests=args.requests, rate=args.rate, slots=args.slots,
        max_len=args.max_len, block_size=args.block_size,
        prefill_chunk=args.prefill_chunk, kv_quant=args.kv_quant,
        num_blocks=args.num_blocks, model_size=args.model_size,
        transport=args.transport, seed=args.seed,
        prefix_overlap=args.prefix_overlap, spec_k=args.spec_k)
    recs = []
    if args.prefill_storm:
        recs = run_storm_bench(
            roles=args.roles, requests=args.requests, rate=args.rate,
            slots=args.slots, max_len=args.max_len,
            block_size=args.block_size,
            prefill_chunk=args.prefill_chunk, kv_quant=args.kv_quant,
            wire=args.kv_wire, seed=args.seed,
            prefix_overlap=(args.prefix_overlap
                            if args.prefix_overlap > 0 else 0.6),
            affinity=not args.no_affinity)
        if args.out:
            with open(args.out, "a") as f:
                for r in recs:
                    f.write(json.dumps(r) + "\n")
        return 0
    if args.prefix_compare:
        off = run_bench(prefix_cache=False, **kw)
        on = run_bench(prefix_cache=True, **kw)
        recs += [off, on]
        # Both are higher-is-better: a regression in either shows up as
        # a drop in "value".
        common = {k: on[k] for k in
                  ("transport", "requests", "arrival_rate_hz", "slots",
                   "max_len", "block_size", "prefill_chunk", "kv_quant",
                   "model", "prefix_overlap", "prefix_cache", "spec_k")}
        recs.append(dict(common, metric="serve_prefix_hit_rate",
                         value=on["prefix_hit_rate"], unit="ratio",
                         vs_baseline=None, proxy=True))
        if off["ttft_mean_s"] and on["ttft_mean_s"]:
            recs.append(dict(
                common, metric="serve_prefix_ttft_speedup",
                value=round(off["ttft_mean_s"] / on["ttft_mean_s"], 4),
                unit="x", vs_baseline=None, proxy=True,
                ttft_mean_off_s=off["ttft_mean_s"],
                ttft_mean_on_s=on["ttft_mean_s"]))
        for r in recs[2:]:
            print(json.dumps(r), flush=True)
    else:
        recs.append(run_bench(prefix_cache=args.prefix_cache, **kw))
    if args.out:
        with open(args.out, "a") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
