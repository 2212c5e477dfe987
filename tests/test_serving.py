"""hvd.serving: continuous-batching engine, paged KV cache, scheduler.

Acceptance pins (ISSUE 4):

* engine single-request output is TOKEN-IDENTICAL to offline
  ``generate()`` / ``t5_generate()`` for all three families — the
  decode-registry factoring makes this hold by construction;
* requests of different lengths admitted mid-flight trigger EXACTLY ONE
  jit compile of the decode step (and one of the chunked-prefill step);
* paged-cache peak block usage stays strictly below the dense
  ``B x T_max`` equivalent, and an under-provisioned pool still serves;
* scheduler invariants: slot-pool accounting (no double-assign, no
  leak), deadline expiry, backpressure rejection, block refcounts under
  randomized admit/evict.
"""

import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.generate import generate, t5_generate
from horovod_tpu.serving.cache import BlockManager
from horovod_tpu.serving.engine import InferenceEngine
from horovod_tpu.serving.replica import Dispatcher
from horovod_tpu.serving.scheduler import (
    Request, RequestQueue, RequestStatus, SlotPool,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# shared models (module scope: init once, reuse across engines)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt2_setup():
    from horovod_tpu.models.gpt2 import GPT2, GPT2Config
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 4), jnp.int32))["params"]
    return model, params, cfg


@pytest.fixture(scope="module")
def llama_setup():
    from horovod_tpu.models.llama import Llama, LlamaConfig
    cfg = LlamaConfig.tiny(num_kv_heads=2, dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 4), jnp.int32))["params"]
    return model, params, cfg


@pytest.fixture(scope="module")
def t5_setup():
    from horovod_tpu.models.t5 import T5, T5Config
    cfg = T5Config.tiny(dtype=jnp.float32)
    model = T5(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 6), jnp.int32),
                        jnp.zeros((1, 1), jnp.int32))["params"]
    return model, params, cfg


# ---------------------------------------------------------------------------
# scheduler primitives (no jax)
# ---------------------------------------------------------------------------

class TestSlotPool:
    def test_randomized_accounting(self, rng):
        pool = SlotPool(5)
        held = set()
        for _ in range(400):
            if rng.random() < 0.55:
                s = pool.acquire()
                if s is not None:
                    assert s not in held, "double-assigned slot"
                    held.add(s)
                else:
                    assert len(held) == 5
            elif held:
                s = held.pop()
                pool.release(s)
            pool.check()
        for s in list(held):
            pool.release(s)
        assert pool.free_count == 5 and pool.busy_count == 0

    def test_double_release_raises(self):
        pool = SlotPool(2)
        s = pool.acquire()
        pool.release(s)
        with pytest.raises(RuntimeError, match="not held"):
            pool.release(s)

    def test_exhaustion_returns_none(self):
        pool = SlotPool(1)
        assert pool.acquire() is not None
        assert pool.acquire() is None


class TestRequestQueue:
    def test_priority_then_fcfs(self):
        q = RequestQueue(16)
        lo1 = q.submit(Request([1], 1, priority=0))
        hi = q.submit(Request([1], 1, priority=5))
        lo2 = q.submit(Request([1], 1, priority=0))
        assert q.pop_ready() is hi
        assert q.pop_ready() is lo1
        assert q.pop_ready() is lo2
        assert q.pop_ready() is None

    def test_requeue_preserves_fcfs(self):
        q = RequestQueue(16)
        a = q.submit(Request([1], 1))
        b = q.submit(Request([1], 1))
        first = q.pop_ready()
        assert first is a
        q.requeue(first)             # engine had no blocks for it
        assert q.pop_ready() is a and q.pop_ready() is b

    def test_backpressure_rejects_with_reason(self):
        q = RequestQueue(2)
        q.submit(Request([1], 1))
        q.submit(Request([1], 1))
        r = q.submit(Request([1], 1))
        assert r.status == RequestStatus.REJECTED
        assert "backpressure" in r.reason
        assert r.result(0.1) == []           # terminal: result unblocks

    def test_deadline_expires_at_pop(self):
        q = RequestQueue(4)
        dead = q.submit(Request([1], 1, deadline_s=0.0))
        live = q.submit(Request([1], 1))
        assert q.pop_ready() is live
        assert dead.status == RequestStatus.EXPIRED

    def test_cancel_queued_skipped(self):
        q = RequestQueue(4)
        a = q.submit(Request([1], 1))
        b = q.submit(Request([1], 1))
        a.cancel()
        assert a.status == RequestStatus.CANCELLED
        assert q.pop_ready() is b

    def test_close_rejects_everything(self):
        q = RequestQueue(4)
        a = q.submit(Request([1], 1))
        q.close("engine shut down")
        assert a.status == RequestStatus.REJECTED
        late = q.submit(Request([1], 1))
        assert late.status == RequestStatus.REJECTED

    def test_cancelled_corpses_do_not_consume_backpressure(self):
        """Cancelled entries linger in the heap until a pop prunes
        them; the bound must count live requests, not corpses."""
        q = RequestQueue(2)
        a = q.submit(Request([1], 1))
        b = q.submit(Request([1], 1))
        a.cancel()
        b.cancel()
        c = q.submit(Request([1], 1))
        assert c.status == RequestStatus.QUEUED
        assert q.pop_ready() is c

    def test_try_submit_never_finalizes(self):
        q = RequestQueue(1)
        q.submit(Request([1], 1))
        r = Request([1], 1)
        assert not q.try_submit(r)
        assert r.status == RequestStatus.QUEUED   # untouched: retry-able

    def test_cancel_beats_admission_race(self):
        """The atomic QUEUED->RUNNING gate: a request cancelled in the
        pop->admit window must stay cancelled, never be resurrected
        into a running lane (status flapping after result() returned)."""
        r = Request([1], 1)
        r.cancel()
        assert r.status == RequestStatus.CANCELLED
        assert not r.start_running()
        ok = Request([1], 1)
        assert ok.start_running()
        assert ok.status == RequestStatus.RUNNING
        ok.cancel()                               # mid-flight: flagged
        assert ok.status == RequestStatus.RUNNING
        assert ok._cancel_requested

    def test_terminal_callback_fires_exactly_once(self):
        fired = []
        r = Request([1], 1)
        r._on_terminal = fired.append
        r._finish(RequestStatus.EXPIRED, "x")
        r._finish(RequestStatus.DONE)             # ignored: terminal
        r.cancel()                                # ignored: terminal
        assert fired == [r] and r.status == RequestStatus.EXPIRED


class TestBlockManager:
    def test_randomized_admit_evict_refcounts(self, rng):
        bs, max_b = 4, 6
        mgr = BlockManager(num_blocks=20, block_size=bs, slots=5,
                           max_blocks_per_slot=max_b)
        live = {}                     # slot -> (reserved_tokens, next_pos)
        for _ in range(600):
            r = rng.random()
            free_slots = [s for s in range(5) if s not in live]
            if r < 0.4 and free_slots:
                tokens = int(rng.integers(1, bs * max_b + 1))
                if mgr.can_reserve(tokens):
                    s = free_slots[0]
                    mgr.reserve(s, tokens)
                    live[s] = [tokens, 0]
            elif r < 0.8 and live:
                s = list(live)[int(rng.integers(len(live)))]
                tokens, pos = live[s]
                if pos < tokens:
                    mgr.ensure(s, pos)
                    live[s][1] += 1
            elif live:
                s = list(live)[int(rng.integers(len(live)))]
                mgr.release(s)
                del live[s]
            mgr.check()
            assert mgr.blocks_in_use <= mgr.capacity
        for s in list(live):
            mgr.release(s)
        mgr.check()
        assert mgr.blocks_in_use == 0
        assert mgr.peak_blocks_in_use <= mgr.capacity

    def test_reserve_twice_raises(self):
        mgr = BlockManager(8, 4, 2, 3)
        mgr.reserve(0, 8)
        with pytest.raises(RuntimeError, match="already holds"):
            mgr.reserve(0, 4)

    def test_over_reserve_raises(self):
        mgr = BlockManager(5, 4, 2, 4)       # capacity 4 blocks
        mgr.reserve(0, 12)                   # 3 blocks
        assert not mgr.can_reserve(8)
        with pytest.raises(RuntimeError, match="over-reserved"):
            mgr.reserve(1, 8)

    def test_ensure_beyond_slot_capacity_raises(self):
        mgr = BlockManager(8, 4, 2, 2)
        mgr.reserve(0, 8)
        with pytest.raises(IndexError):
            mgr.ensure(0, 8)                 # block 2 of a 2-block slot

    def test_ensure_allocates_lazily_and_once(self):
        mgr = BlockManager(8, 4, 2, 3)
        mgr.reserve(0, 12)
        assert mgr.blocks_in_use == 0        # reservation != allocation
        assert mgr.ensure(0, 0) and not mgr.ensure(0, 1)   # same block
        assert mgr.ensure(0, 4)
        assert mgr.blocks_in_use == 2
        mgr.release(0)
        assert mgr.blocks_in_use == 0
        mgr.check()


# ---------------------------------------------------------------------------
# engine parity: token-identical to offline generation (acceptance)
# ---------------------------------------------------------------------------

class TestEngineParity:
    def test_gpt2_token_identical(self, gpt2_setup, rng):
        model, params, cfg = gpt2_setup
        prompt = rng.integers(1, cfg.vocab_size, 7)
        want = np.asarray(generate(
            model, params, jnp.asarray([prompt], jnp.int32), 9))[0, 7:]
        eng = InferenceEngine(model, params, slots=3, max_len=32,
                              block_size=4, prefill_chunk=4)
        req = eng.submit(list(prompt), 9)
        eng.run_until_idle()
        assert req.result(1) == list(want)
        assert req.status == RequestStatus.DONE
        # observability rode along: latency histograms + request counters
        snap = __import__("horovod_tpu").metrics()
        assert any(s["labels"].get("status") == "done"
                   for s in snap["counters"]["serve_requests_total"])
        assert snap["histograms"]["serve_ttft_seconds"][0]["count"] >= 1
        assert snap["histograms"]["serve_queue_wait_seconds"][0][
            "count"] >= 1

    def test_llama_gqa_token_identical(self, llama_setup, rng):
        model, params, cfg = llama_setup
        prompt = rng.integers(1, cfg.vocab_size, 5)
        want = np.asarray(generate(
            model, params, jnp.asarray([prompt], jnp.int32), 8))[0, 5:]
        eng = InferenceEngine(model, params, slots=2, max_len=16,
                              block_size=4, prefill_chunk=3)
        req = eng.submit(list(prompt), 8)
        eng.run_until_idle()
        assert req.result(1) == list(want)

    def test_t5_token_identical(self, t5_setup, rng):
        model, params, cfg = t5_setup
        src = rng.integers(2, cfg.vocab_size, 6)
        want = np.asarray(t5_generate(
            model, params, jnp.asarray([src], jnp.int32), 7))[0]
        eng = InferenceEngine(model, params, slots=2, max_len=16,
                              block_size=4, prefill_chunk=2,
                              max_src_len=6)
        req = eng.submit(None, 7, src=list(src))
        eng.run_until_idle()
        assert req.result(1) == list(want)


class TestCacheDonation:
    """On the chip the engine donates its cache to every dispatch, so the
    pools it passed in are DELETED when the call returns; the CPU runtime
    ignores donation, and no test ever ran with it. These tests delete the
    input pools by hand after each dispatch, as the chip does."""

    @staticmethod
    def _donate_like_the_chip(eng):
        for name in ("_decode_jit", "_prefill_jit"):
            def run(params, cache, *rest, _jitted=getattr(eng, name)):
                out = jax.block_until_ready(_jitted(params, cache, *rest))
                for pool in (cache.kp, cache.vp, cache.ks, cache.vs):
                    if pool is not None:
                        pool.delete()
                return out
            setattr(eng, name, run)

    @pytest.mark.parametrize("kv_quant", [None, "int8"])
    def test_serving_and_migration_survive_deleted_inputs(
            self, gpt2_setup, rng, kv_quant):
        model, params, cfg = gpt2_setup
        eng = InferenceEngine(model, params, slots=3, max_len=48,
                              block_size=4, prefill_chunk=4,
                              prefix_cache=True, kv_quant=kv_quant,
                              name=f"donate-{kv_quant}")
        self._donate_like_the_chip(eng)
        a = list(rng.integers(1, cfg.vocab_size, 13))
        b = a[:8] + list(rng.integers(1, cfg.vocab_size, 5))   # shares 2 blocks
        first = eng.submit(a, 6)
        eng.run_until_idle()
        second = eng.submit(b, 6)                # prefix hit + copy-on-write
        # KV migration reads the cache between dispatches (export_kv) and
        # replaces it outside the jit (import_blocks).
        half = eng.submit(a, 6, prefill_only=True)
        eng.run_until_idle()
        grafted = eng.admit_prefilled(a, 6, *half.kv_export)
        eng.run_until_idle()
        assert eng.stats()["prefix"]["hits"] >= 1
        assert grafted.result(1) == first.result(1)
        if kv_quant is None:                     # fp32 pool: token parity
            for prompt, req in ((a, first), (b, second)):
                want = np.asarray(generate(
                    model, params, jnp.asarray([prompt], jnp.int32),
                    6))[0, len(prompt):]
                assert req.result(1) == list(want)
        assert eng.decode_compiles == 1 and eng.prefill_compiles == 1

    def test_a_failed_dispatch_fails_the_engine_cleanly(self, gpt2_setup):
        # The dispatch consumed the cache and then raised: nothing the
        # failure path or close() does may touch the deleted buffers.
        model, params, cfg = gpt2_setup
        eng = InferenceEngine(model, params, slots=2, max_len=32,
                              block_size=4, prefill_chunk=1,
                              name="donate-fail")
        self._donate_like_the_chip(eng)
        consume = eng._decode_jit

        def consume_then_raise(*args):
            consume(*args)
            raise RuntimeError("device fell over")

        eng._decode_jit = consume_then_raise
        req = eng.submit([5, 6, 7], 4)
        eng.start()
        req.result(30)                           # terminal, not hung
        assert req.status == RequestStatus.FAILED and req.retryable
        assert "device fell over" in eng.failed
        eng.close()
        assert eng.stats()["active"] == 0


class TestContinuousBatching:
    def test_midflight_admission_one_compile_paged_savings(
            self, llama_setup, rng):
        """THE acceptance test: requests of different lengths join
        mid-flight; the decode step compiles exactly once; per-request
        outputs are token-identical to offline generate(); and the
        paged cache's peak block usage stays strictly below the dense
        B x T_max equivalent — on a pool deliberately sized BELOW dense,
        which a (B, T_max) cache could not even start with."""
        model, params, cfg = llama_setup
        slots, max_len, bs = 3, 32, 4
        dense_blocks = slots * (max_len // bs)           # 24
        eng = InferenceEngine(model, params, slots=slots, max_len=max_len,
                              block_size=bs, prefill_chunk=4,
                              num_blocks=dense_blocks // 2 + 1)  # 13
        lengths = [(9, 6), (3, 10), (6, 4), (12, 5), (2, 8)]
        prompts = [list(rng.integers(1, cfg.vocab_size, p))
                   for p, _ in lengths]
        reqs = [eng.submit(prompts[0], lengths[0][1])]
        eng.step_once(); eng.step_once()                 # noqa: E702
        reqs.append(eng.submit(prompts[1], lengths[1][1]))
        eng.step_once()
        reqs.append(eng.submit(prompts[2], lengths[2][1]))
        reqs.append(eng.submit(prompts[3], lengths[3][1]))
        eng.step_once()
        reqs.append(eng.submit(prompts[4], lengths[4][1]))
        eng.run_until_idle()

        for p, (plen, n), req in zip(prompts, lengths, reqs):
            want = np.asarray(generate(
                model, params, jnp.asarray([p], jnp.int32), n))[0, plen:]
            assert req.result(1) == list(want), req.id

        assert eng.decode_compiles == 1, \
            f"decode step recompiled: {eng.decode_compiles}"
        assert eng.prefill_compiles == 1
        assert eng.manager.peak_blocks_in_use < dense_blocks
        assert eng.manager.capacity < dense_blocks       # under-provisioned
        eng.manager.check()
        assert eng.manager.blocks_in_use == 0            # all recycled

    def test_prefill_chunk_one_single_program(self, llama_setup, rng):
        """prefill_chunk=1 rides everything on the decode step: no
        second program is ever compiled."""
        model, params, cfg = llama_setup
        eng = InferenceEngine(model, params, slots=2, max_len=16,
                              block_size=4, prefill_chunk=1)
        prompt = list(rng.integers(1, cfg.vocab_size, 6))
        want = np.asarray(generate(
            model, params, jnp.asarray([prompt], jnp.int32), 5))[0, 6:]
        req = eng.submit(prompt, 5)
        eng.run_until_idle()
        assert req.result(1) == list(want)
        assert eng.decode_compiles == 1 and eng.prefill_compiles == 0


class TestQuantizedKV:
    @pytest.mark.parametrize("wire", ["int8", "fp8"])
    def test_quantized_blocks_serve(self, llama_setup, rng, wire):
        model, params, cfg = llama_setup
        eng = InferenceEngine(model, params, slots=2, max_len=16,
                              block_size=4, prefill_chunk=1,
                              kv_quant=wire)
        assert eng._cache.kp.dtype == (
            jnp.int8 if wire == "int8" else jnp.float8_e4m3fn)
        prompt = list(rng.integers(1, cfg.vocab_size, 5))
        req = eng.submit(prompt, 6)
        eng.run_until_idle()
        assert req.status == RequestStatus.DONE
        assert len(req.tokens) == 6
        assert all(0 <= t < cfg.vocab_size for t in req.tokens)


# ---------------------------------------------------------------------------
# engine-level scheduling behaviour
# ---------------------------------------------------------------------------

class TestEngineScheduling:
    def test_submit_validation_no_compile(self, gpt2_setup):
        model, params, cfg = gpt2_setup
        eng = InferenceEngine(model, params, slots=2, max_len=16,
                              block_size=4, queue_limit=2,
                              prefill_chunk=1)
        too_long = eng.submit([1] * 10, 10)
        assert too_long.status == RequestStatus.REJECTED
        assert "exceeds max_len" in too_long.reason
        empty = eng.submit([], 4)
        assert empty.status == RequestStatus.REJECTED
        eng.submit([1, 2], 4)
        eng.submit([1, 2], 4)
        full = eng.submit([1, 2], 4)
        assert full.status == RequestStatus.REJECTED
        assert "backpressure" in full.reason
        assert eng.decode_compiles == 0      # validation is host-only

    def test_oversized_block_need_rejected_not_livelocked(
            self, gpt2_setup):
        """A request whose worst case exceeds POOL capacity (legal with
        an under-provisioned pool) must be rejected at submit — _admit
        would otherwise requeue it forever, head-of-line blocking the
        queue behind it."""
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=2, max_len=64,
                              block_size=16, num_blocks=3,   # capacity 2
                              prefill_chunk=1)
        giant = eng.submit([1, 2, 3], 60)        # needs 4 blocks
        assert giant.status == RequestStatus.REJECTED
        assert "KV blocks" in giant.reason
        small = eng.submit([1, 2, 3], 8)         # 1 block: fine
        eng.run_until_idle()
        assert small.status == RequestStatus.DONE

    def test_bad_sampling_params_rejected_at_submit(self, gpt2_setup):
        """Malformed top_k/temperature must reject at submit, not crash
        the engine (and every in-flight neighbour) at commit time."""
        model, params, cfg = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=32,
                              block_size=4, prefill_chunk=1)
        bad_k = eng.submit([1, 2], 4, temperature=1.0,
                           top_k=cfg.vocab_size + 100)
        assert bad_k.status == RequestStatus.REJECTED
        assert "top_k" in bad_k.reason
        neg_t = eng.submit([1, 2], 4, temperature=-0.5)
        assert neg_t.status == RequestStatus.REJECTED
        ok = eng.submit([1, 2], 4, temperature=1.0, top_k=5, seed=0)
        eng.run_until_idle()
        assert ok.status == RequestStatus.DONE

    def test_t5_requires_src(self, t5_setup):
        model, params, _ = t5_setup
        eng = InferenceEngine(model, params, slots=1, max_len=8,
                              block_size=4, prefill_chunk=1,
                              max_src_len=6)
        r = eng.submit(None, 4)
        assert r.status == RequestStatus.REJECTED
        assert "src" in r.reason
        long_src = eng.submit(None, 4, src=list(range(2, 12)))
        assert long_src.status == RequestStatus.REJECTED

    def test_t5_explicit_empty_prompt_gets_bos(self, t5_setup):
        """prompt=[] must behave like prompt=None (substitute the pad/
        BOS token), not crash the engine loop at the first step."""
        model, params, cfg = t5_setup
        eng = InferenceEngine(model, params, slots=1, max_len=8,
                              block_size=4, prefill_chunk=1,
                              max_src_len=6)
        r = eng.submit([], 3, src=[2, 3, 4])
        assert r.status == RequestStatus.QUEUED
        eng.run_until_idle()
        assert r.status == RequestStatus.DONE and len(r.tokens) == 3
        assert eng.alive

    def test_deadline_expired_in_queue(self, gpt2_setup):
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=16,
                              block_size=4, prefill_chunk=1)
        r = eng.submit([1, 2, 3], 4, deadline_s=0.0)
        eng.step_once()
        assert r.status == RequestStatus.EXPIRED
        assert "queued" in r.reason

    def test_deadline_mid_flight_partial_tokens(self, gpt2_setup):
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=64,
                              block_size=4, prefill_chunk=1)
        r = eng.submit([1, 2, 3], 40, deadline_s=3600.0)
        for _ in range(8):
            eng.step_once()
        assert r.status == RequestStatus.RUNNING and r.tokens
        r.deadline = time.monotonic() - 1.0      # deadline passes
        eng.step_once()
        assert r.status == RequestStatus.EXPIRED
        assert 0 < len(r.tokens) < 40            # partial output kept
        eng.manager.check()
        assert eng.manager.blocks_in_use == 0    # slot recycled

    def test_cancel_mid_flight(self, gpt2_setup):
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=64,
                              block_size=4, prefill_chunk=1)
        r = eng.submit([1, 2, 3], 40)
        for _ in range(6):
            eng.step_once()
        r.cancel()
        eng.step_once()
        assert r.status == RequestStatus.CANCELLED
        assert r.result(0.1) == r.tokens         # unblocked, partial

    def test_priority_admitted_first(self, gpt2_setup):
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=32,
                              block_size=4, prefill_chunk=1)
        runner = eng.submit([1, 2], 3)
        eng.step_once()                          # runner occupies the slot
        lo = eng.submit([1, 2], 2, priority=0)
        hi = eng.submit([1, 2], 2, priority=5)
        eng.run_until_idle()
        assert runner.status == RequestStatus.DONE
        assert hi.t_admit < lo.t_admit           # priority jumped FCFS

    def test_streaming_on_token(self, gpt2_setup):
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=32,
                              block_size=4, prefill_chunk=1)
        seen = []
        r = eng.submit([1, 2, 3], 6,
                       on_token=lambda req, t: seen.append(t))
        eng.run_until_idle()
        assert seen == r.tokens and len(seen) == 6

    def test_eos_stops_early_and_recycles(self, gpt2_setup):
        """Pick the first greedily generated token as eos: generation
        must stop right there and free the slot's blocks."""
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=32,
                              block_size=4, prefill_chunk=1)
        probe = eng.submit([1, 2, 3], 1)
        eng.run_until_idle()
        eos = probe.tokens[0]
        r = eng.submit([1, 2, 3], 10, eos_id=eos)
        eng.run_until_idle()
        assert r.status == RequestStatus.DONE
        assert r.tokens == [eos]
        assert eng.manager.blocks_in_use == 0

    def test_background_thread_serves(self, gpt2_setup):
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=2, max_len=32,
                              block_size=4, prefill_chunk=1)
        eng.start()
        try:
            reqs = [eng.submit([1, 2, 3 + i], 5) for i in range(4)]
            for r in reqs:
                assert len(r.result(timeout=120)) == 5
                assert r.status == RequestStatus.DONE
        finally:
            eng.stop()

    def test_prefill_chunks_alternate_with_decode(self, llama_setup,
                                                  rng):
        """A sustained stream of long prompts must not freeze lanes
        that are already decoding: chunked prefill dispatches alternate
        with decode dispatches, so an in-flight request keeps
        committing tokens while new prompts prefill."""
        model, params, cfg = llama_setup
        eng = InferenceEngine(model, params, slots=3, max_len=64,
                              block_size=4, prefill_chunk=4)
        decoding = eng.submit(list(rng.integers(1, 255, 2)), 30)
        eng.step_once()                      # past its prompt: decoding
        eng.step_once()
        assert decoding.tokens
        before = len(decoding.tokens)
        # keep at least one long prompt mid-prefill for several steps
        eng.submit(list(rng.integers(1, 255, 20)), 4)
        eng.submit(list(rng.integers(1, 255, 20)), 4)
        for _ in range(6):
            eng.step_once()
        gained = len(decoding.tokens) - before
        assert gained >= 3, (gained, decoding.tokens)   # every other step
        eng.run_until_idle()
        assert decoding.status == RequestStatus.DONE

    def test_terminal_request_accounting_balances(self, gpt2_setup):
        """serve_requests_total{status} must sum to {submitted} even
        for requests that end while still queued (cancel, deadline)."""
        import horovod_tpu as hvd
        hvd.reset_metrics()
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=32,
                              block_size=4, prefill_chunk=1,
                              name="acct")
        done = eng.submit([1, 2, 3], 4)
        queued_cancel = eng.submit([1, 2, 3], 4)
        queued_expire = eng.submit([1, 2, 3], 4, deadline_s=0.0)
        queued_cancel.cancel()
        eng.run_until_idle()
        assert done.status == RequestStatus.DONE
        snap = hvd.metrics()
        by_status = {s["labels"]["status"]: s["value"]
                     for s in snap["counters"]["serve_requests_total"]
                     if s["labels"].get("engine") == "acct"}
        assert by_status["submitted"] == 3
        assert by_status.get("done") == 1
        assert by_status.get("cancelled") == 1
        assert by_status.get("expired") == 1

    def test_close_resolves_everything(self, gpt2_setup):
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=32,
                              block_size=4, prefill_chunk=1)
        a = eng.submit([1, 2], 8)
        b = eng.submit([1, 2], 8)
        eng.step_once()
        eng.close()
        assert a.status.terminal and b.status.terminal
        late = eng.submit([1, 2], 2)
        assert late.status == RequestStatus.REJECTED

    def test_drain_finishes_inflight_and_rejects_new(self, gpt2_setup):
        """drain() = finish everything accepted so far, shed everything
        after: the documented graceful-shutdown contract."""
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=32,
                              block_size=4, prefill_chunk=1)
        inflight = eng.submit([1, 2, 3], 5)
        queued = eng.submit([1, 2, 3], 5)
        eng.step_once()
        import threading
        results = []
        t = threading.Thread(
            target=lambda: results.append(eng.drain(timeout=120)))
        t.start()
        while not eng._draining:
            time.sleep(0.001)
        late = eng.submit([1, 2], 2)
        assert late.status == RequestStatus.REJECTED
        assert "draining" in late.reason
        t.join(timeout=120)
        assert results == [True]
        assert inflight.status == RequestStatus.DONE
        assert queued.status == RequestStatus.DONE


class TestDispatcher:
    def test_least_loaded_routing_and_failover(self, gpt2_setup):
        model, params, _ = gpt2_setup
        e0 = InferenceEngine(model, params, slots=1, max_len=32,
                             block_size=4, prefill_chunk=1, name="d0")
        e1 = InferenceEngine(model, params, slots=1, max_len=32,
                             block_size=4, prefill_chunk=1, name="d1")
        disp = Dispatcher([e0, e1])
        # routing: least-loaded alternates while loads tie
        reqs = [disp.submit([1, 2, 3], 4) for _ in range(4)]
        assert e0.load() == 2 and e1.load() == 2
        e0.step_once()                       # e0 starts one request
        running = [r for r in reqs if r.status == RequestStatus.RUNNING]
        assert len(running) == 1
        # kill e0: its running request fails with the reason, its queued
        # one is adopted by the survivor automatically (same handle)
        e0._fail("simulated replica loss")
        assert not e0.alive
        e1.run_until_idle()
        done = [r for r in reqs if r.status == RequestStatus.DONE]
        failed = [r for r in reqs if r.status == RequestStatus.FAILED]
        assert len(done) == 3 and failed == running
        assert "replica loss" in failed[0].reason
        assert all(r.served_by == "d1" for r in done
                   if r not in running)
        # dead fleet rejects with a reason instead of hanging — and the
        # handle reflects the caller's REAL spec for log correlation
        e1._fail("second loss")
        r = disp.submit([1, 2], 32, request_id="corr-1", priority=3)
        assert r.status == RequestStatus.REJECTED
        assert "no live replicas" in r.reason
        assert r.id == "corr-1" and r.max_new_tokens == 32
        assert r.priority == 3 and r.retryable

    def test_adoption_revalidates_against_survivor_geometry(
            self, gpt2_setup):
        """Engines in a group may differ (max_len, pool size); failover
        must re-check each orphan against the ADOPTER — blindly
        enqueueing a too-big request would wedge or crash the
        survivor. A request no survivor can hold fails with the
        reason; the survivor keeps serving."""
        model, params, _ = gpt2_setup
        big = InferenceEngine(model, params, slots=1, max_len=64,
                              block_size=4, prefill_chunk=1, name="big")
        small = InferenceEngine(model, params, slots=1, max_len=16,
                                block_size=4, prefill_chunk=1,
                                name="small")
        disp = Dispatcher([big, small])
        giant = disp.submit([1, 2, 3], 30)       # only "big" fits it
        assert giant.served_by is None and big.load() == 1
        big._fail("simulated loss")
        assert giant.status == RequestStatus.FAILED
        assert "no survivor can adopt" in giant.reason
        ok = disp.submit([1, 2, 3], 4)           # survivor still serves
        small.run_until_idle()
        assert ok.status == RequestStatus.DONE
        assert small.alive

    def test_rejected_on_full_replica_retries_peer(self, gpt2_setup):
        model, params, _ = gpt2_setup
        e0 = InferenceEngine(model, params, slots=1, max_len=32,
                             block_size=4, queue_limit=1,
                             prefill_chunk=1, name="f0")
        e1 = InferenceEngine(model, params, slots=1, max_len=32,
                             block_size=4, queue_limit=4,
                             prefill_chunk=1, name="f1")
        disp = Dispatcher([e0, e1])
        accepted = [disp.submit([1, 2], 2) for _ in range(4)]
        assert all(r.status != RequestStatus.REJECTED for r in accepted)


class TestReplicaSpool:
    def test_permanent_rejection_published_not_respooled(
            self, gpt2_setup, tmp_path):
        """A spool request no replica can EVER serve (validation
        reject) must land in done/ with its reason — respooling it
        would bounce between replicas forever while the client polls
        done/ for nothing."""
        from horovod_tpu.serving.replica import (
            ReplicaServer, read_result, submit_file_request)
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=16,
                              block_size=4, prefill_chunk=1)
        srv = ReplicaServer(str(tmp_path), 0, eng, heartbeat_s=0.2)
        rid = submit_file_request(str(tmp_path), [1, 2, 3], 60)  # > max_len
        ok = submit_file_request(str(tmp_path), [1, 2, 3], 4)
        for _ in range(15):
            srv.poll_once()
            eng.step_once()
        res = read_result(str(tmp_path), rid)
        assert res is not None and res["status"] == "rejected"
        assert "max_len" in res["reason"]
        assert read_result(str(tmp_path), ok)["status"] == "done"
        assert not os.listdir(tmp_path / "spool")   # nothing bouncing
        eng.stop()

    def test_dead_engine_retires_replica_and_returns_claims(
            self, gpt2_setup, tmp_path):
        """When the engine dies, the replica must stop claiming, hand
        unfinished claims back to the spool, and withdraw its heartbeat
        so peers fail over immediately — not keep out-claiming healthy
        replicas just to bounce requests."""
        from horovod_tpu.serving.replica import (
            ReplicaServer, submit_file_request)
        model, params, _ = gpt2_setup
        eng = InferenceEngine(model, params, slots=1, max_len=32,
                              block_size=4, prefill_chunk=1)
        srv = ReplicaServer(str(tmp_path), 0, eng, heartbeat_s=0.2)
        rid = submit_file_request(str(tmp_path), [1, 2, 3], 20)
        srv.poll_once()                       # claim it
        assert os.listdir(tmp_path / "claim" / "rank0")
        eng._fail("simulated death")
        srv.poll_once()                       # retire
        assert [f"{rid}.json"] == os.listdir(tmp_path / "spool")
        assert not os.listdir(tmp_path / "claim" / "rank0")
        assert not os.path.exists(tmp_path / "hb" / "rank0.json")
        assert srv._stop.is_set()             # loop would exit


# ---------------------------------------------------------------------------
# two-process failover smoke (make serve-smoke)
# ---------------------------------------------------------------------------

class TestTwoProcessSmoke:
    def test_kill_one_replica_survivor_drains(self, tmp_path):
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        try:
            import serve_smoke
        finally:
            sys.path.remove(os.path.join(_REPO, "tools"))
        # run_smoke returns (rc, failure_text) — the text feeds the
        # rendezvous-flake retry in tools/smoke_util.py.
        rc, text = serve_smoke.run_smoke(str(tmp_path))
        assert rc == 0, text


# ---------------------------------------------------------------------------
# the load generator's command line (tools/serve_bench.py, loaded by path:
# tools/ is not a package)
# ---------------------------------------------------------------------------

def test_serve_bench_tool_parser():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "hvd_serve_bench", os.path.join(_REPO, "tools", "serve_bench.py"))
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    args = sb._build_parser().parse_args(
        ["--requests", "4", "--rate", "9", "--kv-quant", "int8"])
    assert args.requests == 4 and args.rate == 9.0
    assert args.kv_quant == "int8"
    with pytest.raises(SystemExit):
        sb._build_parser().parse_args(["--kv-quant", "int4"])
