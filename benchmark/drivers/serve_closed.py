"""Driver ``serve_closed``: a fixed pool of callers, each waiting for its
reply before it sends its next request.

The system under test is ``InferenceEngine`` behind ``SocketReplicaServer``
and ``RemoteDispatcher`` in this one process. The traffic file gives the
number of callers and the two lognormal length distributions. A pool of
(prompt, output) length pairs is drawn once with the file's ``sizes_seed``,
so every ``--seed`` offers the same multiset of work; ``--seed`` shuffles the
order and draws the tokens. Callers take the next pair off the shuffled,
cycled pool. Each caller stamps every token as the stream wire delivers it
(``on_token``), so the times are the client's.

Set-up ends with a ramp: the window opens once every caller has finished
one request, with the lanes out of step. At its end the callers finish the
request they are in and stop, so every request submitted in the window is
completed and judged.

The check, after the window: a few completed requests are scored by the
plain fp32 reference's full forward pass, padded to one shape; each token
the engine chose must lie in the near-tie band of the reference's top
logit at its position.
"""

import threading
import time
from types import SimpleNamespace

import numpy as np

import stats


def _lognormal_lengths(rng, spec, n):
    raw = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def _pool(ctx):
    """The seed's order of the traffic file's fixed pool of length pairs."""
    traffic = ctx.traffic
    sizes = np.random.default_rng(traffic["sizes_seed"])
    pairs = list(zip(
        _lognormal_lengths(sizes, traffic["prompt_len"], traffic["pool"]),
        _lognormal_lengths(sizes, traffic["output_len"], traffic["pool"])))
    order = np.random.default_rng([ctx.seed, 0]).permutation(len(pairs))
    return [(int(pairs[i][0]), int(pairs[i][1])) for i in order]


class _Callers:
    """The closed loop: ``n`` threads over one dispatcher."""

    def __init__(self, ctx, disp, pool):
        self.ctx, self.disp, self.pool = ctx, disp, pool
        self.lock = threading.Lock()
        self.next_index = 0
        self.records = []
        self.errors = []
        self.stop = threading.Event()
        self.ramped = threading.Semaphore(0)
        self.threads = [
            threading.Thread(target=self._run, daemon=True,
                             name=f"bench-caller-{i}")
            for i in range(ctx.traffic["callers"])]

    def _take(self):
        with self.lock:
            index = self.next_index
            self.next_index += 1
        p_len, n_out = self.pool[index % len(self.pool)]
        rng = np.random.default_rng([self.ctx.seed, 1, index])
        prompt = rng.integers(1, self.ctx.config["vocab_size"], p_len)
        return index, [int(t) for t in prompt], n_out

    def _run(self):
        first = True
        try:
            while not self.stop.is_set():
                index, prompt, n_out = self._take()
                stamps = []
                with self.ctx.tracer.span("bench:client"):
                    t_submit = time.perf_counter()
                    h = self.disp.submit(
                        prompt, n_out,
                        deadline_s=self.ctx.traffic["deadline_s"])
                    h.on_token = lambda i, tok, s=stamps: s.append(
                        time.perf_counter())
                    self.disp.wait(h)
                rec = SimpleNamespace(
                    index=index, prompt=prompt, budget=n_out,
                    t_submit=t_submit, stamps=stamps, status=h.status,
                    reason=h.reason, tokens=list(h.tokens), ttft=h.ttft,
                    ttft_client=h.ttft_client)
                with self.lock:
                    self.records.append(rec)
                if first:
                    first = False
                    self.ramped.release()
        except BaseException as e:      # re-raised by the main thread
            self.errors.append(e)
            self.ramped.release()

    def start_and_ramp(self):
        for t in self.threads:
            t.start()
        for _ in self.threads:
            self.ramped.acquire()
        self.raise_errors()

    def finish(self):
        self.stop.set()
        for t in self.threads:
            t.join()
        self.raise_errors()

    def raise_errors(self):
        if self.errors:
            raise self.errors[0]


def _engine_counts(st):
    from horovod_tpu import metrics
    s = st.eng.stats()
    return SimpleNamespace(
        steps=s["steps"], stats=s,
        decode=metrics.counter("serve_steps_total", engine=st.eng.name,
                               phase="decode").value,
        prefill=metrics.counter("serve_steps_total", engine=st.eng.name,
                                phase="prefill").value)


def set_up(ctx):
    from horovod_tpu.serving import (InferenceEngine, RemoteDispatcher,
                                     SocketReplicaServer)
    fam, traffic = ctx.family, ctx.traffic
    ctx.hvd.init(devices=ctx.devices)
    cfg = fam.program_config(ctx.config)
    model = fam.model(cfg)
    run = ctx.config["run"]
    params = fam.make_params(cfg, ctx.seed, run["param_dtype"])
    ctx.jax.block_until_ready(params)
    ctx.log("serve: weights on the device")
    eng = InferenceEngine(model, params, name="bench", **run["engine"])
    ctx.log("serve: engine built")
    # Warm both programs before the server's threads exist: a first
    # compile holds the GIL long enough to trip the client's breakers.
    warm = eng.submit([int(t) for t in np.random.default_rng(
        [ctx.seed, 2]).integers(1, ctx.config["vocab_size"],
                                traffic["warm_prompt_len"])], 2)
    eng.run_until_idle()
    if warm.status.value != "done":
        raise RuntimeError(f"warm-up request: {warm.status} {warm.reason}")
    s = eng.stats()
    ctx.log(f"serve: engine slots {eng.slots} max_len {eng.max_len} block "
            f"{eng.block_size} blocks {eng.num_blocks} prefill_chunk "
            f"{eng.prefill_chunk} spec_k {eng.spec_k}; weights "
            f"{s['param_bytes_per_rank'] / 1e9:.2f} GB, KV pool "
            f"{s['kv_pool_bytes_per_rank'] / 1e9:.2f} GB; cache donated: "
            f"{bool(eng._donate)}")
    srv = SocketReplicaServer(eng, 0).start()
    disp = RemoteDispatcher([srv.address])
    callers = _Callers(ctx, disp, _pool(ctx))
    st = SimpleNamespace(eng=eng, srv=srv, disp=disp, callers=callers)
    t0 = time.perf_counter()
    try:
        callers.start_and_ramp()
    except BaseException:
        _shut_down(st)
        raise
    ctx.log(f"serve: ramp of {time.perf_counter() - t0:.1f} s, every caller "
            f"has finished one request")
    return st


def _shut_down(st):
    st.callers.stop.set()
    st.disp.close()
    st.srv.stop()
    st.eng.close()


def window(ctx, st, seconds):
    try:
        before = _engine_counts(st)
        t0 = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
            ctx.tracer.tick(elapsed)
            time.sleep(min(0.02, seconds - elapsed))
        t1 = time.perf_counter()
        after = _engine_counts(st)
        ctx.tracer.stop()
        st.callers.finish()
    finally:
        _shut_down(st)
    if st.eng.failed is not None:
        raise RuntimeError(f"the engine failed: {st.eng.failed}")

    records = sorted(st.callers.records, key=lambda r: r.t_submit)
    mine = [r for r in records if t0 <= r.t_submit < t1]
    bad = [r for r in mine
           if r.status != "done" or len(r.tokens) != r.budget
           or len(r.stamps) != r.budget]
    for r in bad[:5]:
        ctx.log(f"serve: request {r.index} (prompt {len(r.prompt)}, budget "
                f"{r.budget}): {r.status} {r.reason!r}, {len(r.tokens)} "
                f"tokens, {len(r.stamps)} stamps")
    delivered = sum(1 for r in records for s in r.stamps if t0 <= s < t1)
    gaps = [b - a for r in records
            for a, b in zip(r.stamps, r.stamps[1:]) if t0 <= b < t1]
    ttfts = [r.stamps[0] - r.t_submit for r in mine if r.stamps]
    front = [r.ttft_client - r.ttft for r in mine
             if r.ttft is not None and r.ttft_client is not None]
    steps = after.steps - before.steps
    decode = after.decode - before.decode
    s = after.stats
    steady = (s["decode_compiles"] == 1 and s["prefill_compiles"] == 1)
    ctx.log(f"serve: {len(mine)} requests submitted in {t1 - t0:.3f} s, "
            f"{delivered} tokens delivered, {len(gaps)} gaps; engine steps "
            f"{steps} ({decode} decode, {after.prefill - before.prefill} "
            f"prefill); decode_compiles {s['decode_compiles']}, "
            f"prefill_compiles {s['prefill_compiles']}; blocks peak "
            f"{s['blocks_peak']}/{s['blocks_capacity']}; prefix "
            f"{s['prefix']}")
    st.window_records = mine
    end_to_end = {"serve_tokens_per_s": delivered / (t1 - t0)}
    if gaps:
        end_to_end["itl_p95_ms"] = 1e3 * stats.percentile(gaps, 95)
        ctx.log(f"serve: gap p50 {1e3 * stats.median(gaps):.1f} p95 "
                f"{end_to_end['itl_p95_ms']:.1f} max {1e3 * max(gaps):.1f} ms")
    if ttfts:
        end_to_end["ttft_p90_ms"] = 1e3 * stats.percentile(ttfts, 90)
        ctx.log(f"serve: ttft p50 {1e3 * stats.median(ttfts):.1f} p90 "
                f"{end_to_end['ttft_p90_ms']:.1f} max "
                f"{1e3 * max(ttfts):.1f} ms over {len(ttfts)} requests")
    counters = {"window_s": t1 - t0, "engine_steps": steps,
                "tokens_delivered": delivered,
                "decode_lane_steps": decode * st.eng.slots}
    if front:
        counters["front_ttft_ms"] = 1e3 * stats.median(front)
    return {"attempted": len(mine), "failed": len(bad), "steady": steady,
            "end_to_end": end_to_end, "counters": counters}


def check(ctx, st):
    fam, traffic = ctx.family, ctx.traffic
    pad = traffic["check_pad_len"]
    chosen = [r for r in st.window_records
              if r.status == "done" and len(r.prompt) + len(r.tokens) <= pad]
    chosen = chosen[:traffic["check_requests"]]
    if len(chosen) < traffic["check_requests"]:
        ctx.log(f"serve check: only {len(chosen)} completed requests fit "
                f"{pad} positions")
        return False
    seqs = np.zeros((len(chosen), pad), np.int32)
    for i, r in enumerate(chosen):
        seqs[i, :len(r.prompt) + len(r.tokens)] = r.prompt + r.tokens
    ref = fam.reference_tree(ctx.config, st.eng.params)
    top, picked = (np.asarray(x) for x in fam.reference.ref_score(
        ref, ctx.jax.device_put(seqs, ctx.devices[0]),
        **fam.reference_kwargs(ctx.config)))
    band = traffic["near_tie_band"] * np.maximum(1.0, np.abs(top))
    worst, ok, exact = 0.0, True, 0
    for i, r in enumerate(chosen):
        cols = slice(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens))
        short = (top - picked)[i, cols] / band[i, cols]
        worst = max(worst, float(short.max()))
        exact += int((short <= 0).sum())
        ok = ok and bool(np.isfinite(short).all() and (short <= 1.0).all())
    n = sum(len(r.tokens) for r in chosen)
    ctx.log(f"serve check: {len(chosen)} requests, {n} tokens against the "
            f"fp32 reference: {exact} are its top token; the worst sits at "
            f"{worst:.2f} of the near-tie band below the top logit")
    return ok
