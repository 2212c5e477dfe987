"""InferenceEngine: continuous (in-flight) batching over one jitted step.

The engine owns ``slots`` fixed decode lanes. ONE jitted decode step
advances every occupied lane one token; between steps — plain host
Python, no recompilation — finished requests are evicted and queued
requests admitted into the freed lanes. The jit sees only static shapes:

* ``tok``/``pos`` are ``(slots,)`` vectors — per-slot position indices,
  so lanes at wildly different depths share one program;
* ``active`` masks dead lanes — their writes land in the paged cache's
  trash block and their outputs are ignored on the host;
* the paged block table changes *values* between steps, never shape.

Prefill is chunked and interleaved against decode: a freshly admitted
prompt is teacher-forced ``prefill_chunk`` tokens at a time through a
scanned variant of the same step (decode lanes frozen for the duration
of one chunk — the knob bounds how much a long prompt can stall
in-flight decodes). With ``prefill_chunk=1`` everything rides the decode
step and no second program is ever compiled.

Because both drivers run the SAME registry step functions
(``models/generate.decode_step``), a single-request engine run is
token-identical to offline ``generate()`` — the parity tests in
``tests/test_serving.py`` pin all three families.

Two multipliers ride the same single decode program (PR 12):

* **Shared-prefix caching** (``prefix_cache=True`` /
  ``HOROVOD_SERVE_PREFIX_CACHE=1``): admission matches the prompt
  against the pool's radix index (``serving/cache.py``) and attaches
  already-prefilled preamble blocks refcounted — only the divergent
  tail is prefilled, copy-on-write protects shared blocks, and the
  admission reservation shrinks to the unshared tail. Disabled for T5
  (decoder KV depends on the per-request encoder output).
* **Speculative decode** (``spec_k=k`` / ``HOROVOD_SERVE_SPEC_K=k``):
  an n-gram proposer drafts up to k tokens from the request's own
  prompt + history, and the decode program — ALWAYS the
  ``spec_k + 1``-step verify scan, so ``decode_compiles == 1`` holds —
  accepts the longest prefix matching the model's own greedy chain.
  Greedy lanes only; acceptance keeps token-parity with offline
  ``generate()`` by construction (every accepted token IS the model's
  greedy pick).

Observability (PRs 1–2): ``serve_ttft_seconds`` / ``serve_tpot_seconds``
/ ``serve_queue_wait_seconds`` histograms, ``serve_slots_active`` /
``serve_queue_depth`` / ``serve_blocks_in_use`` gauges, per-request
timeline markers, and every device dispatch is registered in the
pending-collective table so the stall watchdog names a stuck decode
step like it names a stuck allreduce.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from horovod_tpu import metrics, profiler, tracing
from horovod_tpu.models.generate import (
    decode_family, decode_step, decode_verify_step, greedy_token,
    t5_decoder_bias, t5_encode,
)
from horovod_tpu.serving import reqtrace
from horovod_tpu.serving.cache import BlockManager, PagedKVCache, TRASH_BLOCK
from horovod_tpu.serving.scheduler import (
    Request, RequestQueue, RequestStatus, SlotPool,
)

__all__ = ["InferenceEngine"]


class _SlotState:
    """Host-side progress of one running request: ``n_fed`` tokens have
    been fed (prompt first, then the request's own output); the next
    input goes to position ``n_fed``."""

    __slots__ = ("request", "slot", "n_fed", "span", "decode_steps")

    def __init__(self, request: Request, slot: int, span) -> None:
        self.request = request
        self.slot = slot
        self.n_fed = 0
        self.span = span
        self.decode_steps = 0


class InferenceEngine:
    """Continuous-batching engine over one model's decode program.

    Knob defaults come from ``HOROVOD_SERVE_*`` (:mod:`horovod_tpu
    .config`); constructor arguments override. ``num_blocks`` sizes the
    shared KV pool — the default is the dense equivalent (every slot can
    reach ``max_len``); size it *below* ``slots * ceil(max_len /
    block_size)`` to serve the same concurrency in less memory when
    typical requests are shorter than the worst case.
    """

    def __init__(self, model, params, *, slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 kv_quant: Optional[str] = "__env__",
                 prefill_chunk: Optional[int] = None,
                 queue_limit: Optional[int] = None,
                 max_src_len: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 spec_proposer: Optional[str] = None,
                 role: Optional[str] = None,
                 name: str = "engine0"):
        from horovod_tpu.config import get_config
        hcfg = get_config()
        self.name = name
        self.model = model
        self.cfg = model.cfg
        self.family = decode_family(self.cfg)
        self.family.validate(self.cfg)
        # Tensor-parallel serving rides the runtime dp x mp mesh
        # (HOROVOD_MESH): mp > 1 means every rank holds 1/mp of each
        # weight and 1/mp of the KV pool (heads split over mp), and the
        # decode program runs under shard_map with collective matmuls.
        # An uninitialized runtime serves replicated, like always.
        try:
            from horovod_tpu import core as _core
            self._mp = _core.mp_size()
            self._mesh2d = _core.mesh2d() if self._mp > 1 else None
            self._mesh_spec = _core.mesh_spec()
        except Exception:
            self._mp, self._mesh2d, self._mesh_spec = 1, None, None
        if self._mp > 1:
            from horovod_tpu import core as _core
            from horovod_tpu.parallel import mp as _mp
            if self.family.name == "t5":
                raise NotImplementedError(
                    "tensor-parallel serving is implemented for "
                    "decoder-only families; run T5 engines on a "
                    "dp-only mesh")
            if _core.dp_size() != 1:
                raise NotImplementedError(
                    f"tensor-parallel serving needs a dp=1 mesh "
                    f"(every engine rank is one mp shard); got "
                    f"{self._mesh_spec}")
            _mp.validate_tp(self.cfg, self._mp)
        self.slots = int(slots if slots is not None else hcfg.serve_slots)
        self.max_len = int(max_len if max_len is not None
                           else hcfg.serve_max_len)
        self.block_size = int(block_size if block_size is not None
                              else hcfg.serve_block_size)
        self.prefill_chunk = int(prefill_chunk if prefill_chunk is not None
                                 else hcfg.serve_prefill_chunk)
        self.kv_quant = (hcfg.serve_kv_quant if kv_quant == "__env__"
                         else kv_quant) or None
        # Prefix sharing is sound only when a prompt's KV depends on the
        # prompt alone: T5 decoder self-attention K/V are a function of
        # the per-request encoder output through cross-attention, so two
        # requests with identical decoder prompts still have different
        # cache contents — the gate silently disables sharing for T5
        # (speculative decode stays available: the verify chain replays
        # the slot's OWN state, nothing is shared).
        pfx = (hcfg.serve_prefix_cache if prefix_cache is None
               else prefix_cache)
        self.prefix_enabled = bool(pfx) and self.family.name != "t5"
        self.spec_k = int(spec_k if spec_k is not None
                          else hcfg.serve_spec_k)
        self.spec_proposer = str(spec_proposer if spec_proposer is not None
                                 else hcfg.serve_spec_proposer)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.spec_k > 0 and self.spec_proposer != "ngram":
            raise ValueError(f"unknown spec proposer "
                             f"{self.spec_proposer!r}; known: ('ngram',)")
        # Disaggregated serving (serving/disagg.py): "prefill" engines
        # accept only prefill_only requests (run the chunked-prefill
        # program, export the prompt KV, finish DONE/"prefilled"
        # without committing a token); "decode" engines accept grafts
        # via admit_prefilled plus whole requests (the migration-kill
        # fallback re-prefills on a survivor). "both" is monolithic.
        # Role splitting is gated like prefix sharing: T5's decoder KV
        # depends on the per-request encoder output, and migration of
        # an mp-stacked pool is not implemented — refuse loudly rather
        # than serve a role the engine can't honour.
        self.role = str(role if role is not None
                        else hcfg.serve_role).lower()
        if self.role not in ("prefill", "decode", "both"):
            raise ValueError(f"unknown serve role {self.role!r}; "
                             f"known: ('prefill', 'decode', 'both')")
        if self.role != "both" and self.family.name == "t5":
            raise NotImplementedError(
                "disaggregated prefill/decode is not supported for t5 "
                "(decoder KV depends on the per-request encoder "
                "output, so prompt KV cannot be migrated); run t5 "
                "replicas with HOROVOD_SERVE_ROLE=both")
        if self.role != "both" and self._mp > 1:
            raise NotImplementedError(
                "KV migration of an mp-stacked pool is not "
                "implemented; run tensor-parallel engines with "
                "HOROVOD_SERVE_ROLE=both")
        queue_limit = int(queue_limit if queue_limit is not None
                          else hcfg.serve_queue_limit)
        if self.slots < 1 or self.max_len < 2 or self.block_size < 1 \
                or self.prefill_chunk < 1:
            raise ValueError(
                f"bad engine geometry: slots={self.slots}, "
                f"max_len={self.max_len}, block_size={self.block_size}, "
                f"prefill_chunk={self.prefill_chunk}")
        model_max = getattr(self.cfg, "max_seq_len", None)
        if model_max is not None and self.max_len > model_max:
            raise ValueError(
                f"max_len={self.max_len} exceeds the model's "
                f"max_seq_len={model_max}")

        self.max_blocks_per_slot = math.ceil(self.max_len / self.block_size)
        dense_blocks = self.slots * self.max_blocks_per_slot
        self.num_blocks = int(num_blocks if num_blocks is not None
                              else dense_blocks + 1)
        self.manager = BlockManager(self.num_blocks, self.block_size,
                                    self.slots, self.max_blocks_per_slot,
                                    prefix_cache=self.prefix_enabled)

        layers = self.family.num_layers(self.cfg)
        # The LOCAL (per-rank) cache: kv heads split over mp. Pool-byte
        # accounting is snapshotted here — once the cache is mp-stacked
        # its leading dim is the mesh axis, not the pool geometry.
        local_cache = PagedKVCache.create(
            layers, self.family.kv_heads(self.cfg) // self._mp,
            self.family.head_dim(self.cfg), slots=self.slots,
            num_blocks=self.num_blocks, block_size=self.block_size,
            max_blocks_per_slot=self.max_blocks_per_slot,
            dtype=self.cfg.dtype, quant=self.kv_quant)
        self.view_len = local_cache.view_len
        self._pool_bytes = local_cache.pool_bytes
        self._bytes_per_block = local_cache.bytes_per_block

        if self._mp > 1:
            from horovod_tpu.parallel import mp as _mp
            self._mpmod = _mp
            # Every rank's zero-initialized cache is identical, so the
            # stacked layout is a plain broadcast; params are each
            # rank's 1/mp Megatron slice.
            self._cache = _mp.mp_broadcast(local_cache, self._mesh2d)
            self.params = _mp.mp_stack(
                lambda r: _mp.split_params(self.cfg, params,
                                           self._mp, r),
                self._mesh2d)
            self._step = _mp.tp_decode_step(self.cfg)
            self._verify = _mp.tp_decode_verify_step(self.cfg)
        else:
            self._mpmod = None
            self._cache = local_cache
            self.params = jax.tree_util.tree_map(jnp.asarray, params)
            self._step = decode_step(self.cfg)
            self._verify = decode_verify_step(self.cfg)
        self._param_bytes = sum(
            int(l.nbytes) for l in
            jax.tree_util.tree_leaves(self.params)) // self._mp
        self._extras = self._init_extras(max_src_len)

        self.queue = RequestQueue(queue_limit)
        self._slot_pool = SlotPool(self.slots)
        self._states: Dict[int, _SlotState] = {}
        self._lock = threading.RLock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.failed: Optional[str] = None
        self._draining = False
        #: set by the Dispatcher: called with (engine, orphaned queued
        #: requests) when the engine fails, so survivors can adopt them
        #: instead of the queue rejecting them.
        self.on_fail = None
        self.step_count = 0
        self._last_prefill = False
        self._decode_traces = 0
        self._prefill_traces = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        # Prompt-overlap observer: counts admissions whose leading block
        # chunk was seen before, whether or not the prefix cache is on —
        # the doctor compares this against prefix_cache_hit_rate to say
        # "your workload repeats itself; turn the cache on". Bounded
        # fingerprint set; the rate saturates once full, which is fine
        # for a ratio diagnostic.
        self._overlap_seen: set = set()
        self._overlap_hits = 0
        self._overlap_total = 0
        # Migration counters: grafts feed the FLEET-scope prefix hit
        # rate — a grafted admission is a request whose prefill ran on
        # another replica, i.e. a cache hit at fleet scope even though
        # the local radix index never saw the prompt.
        self._graft_admissions = 0
        self._prefill_exports = 0
        self._span = tracing.mint_span("serve_engine", tensor=name,
                                       traced=True)

        # Donate the cache so XLA updates the K/V pools IN PLACE: the
        # caller unconditionally replaces self._cache with the returned
        # one, and without aliasing every token would copy the whole
        # pool (O(pool) per step, 2x peak memory — the opposite of what
        # paging buys). CPU's runtime doesn't implement donation; skip
        # it there to keep test logs warning-free.
        donate = (1,) if jax.default_backend() != "cpu" else ()

        # The decode program is ALWAYS the K-step verify scan (K =
        # spec_k + 1; K == 1 is exactly the classic one-token step):
        # one jitted decode program per engine whatever the speculation
        # knob says, which is how ``decode_compiles == 1`` survives the
        # spec lane. ``cow_src``/``cow_dst`` fold the copy-on-write
        # block copies into the same dispatch — fixed (slots,) vectors
        # padded with trash->trash no-ops, so CoW traffic never changes
        # the program signature either.
        def _decode_body(params, cache, tok_seq, pos0, counts, active,
                         cow_src, cow_dst, extras):
            cache = cache.copy_blocks(cow_src, cow_dst)
            base = active

            def mask_fn(c, lane):
                return c.with_active(base & lane)

            return self._verify(params, cache, tok_seq, pos0, counts,
                                extras, mask_fn)

        # mp > 1: the SAME body runs under shard_map over the mesh's mp
        # axis — the tp steps' psums/all_gathers become collective
        # matmuls inside the one jitted program, which is how
        # decode_compiles == 1 survives tensor parallelism.
        _decode_pure = _decode_body if self._mp == 1 else \
            self._mpmod.wrap_spmd(_decode_body, self._mesh2d)

        def _decode_raw(params, cache, tok_seq, pos0, counts, active,
                        cow_src, cow_dst, extras):
            self._decode_traces += 1          # host effect: fires per TRACE
            profiler.count_trace(f"serve:{name}:decode")
            return _decode_pure(params, cache, tok_seq, pos0, counts,
                                active, cow_src, cow_dst, extras)

        self._decode_pure = _decode_pure
        self._decode_jit = jax.jit(_decode_raw, donate_argnums=donate)
        tracing.note_program("_decode_raw")     # set-up ledger series
        tracing.note_program("_prefill_raw")

        C, V = self.prefill_chunk, self.cfg.vocab_size
        view_len = self.view_len

        def _prefill_body(params, cache, tok_seq, pos0, count, active,
                          cow_src, cow_dst, extras):
            cache = cache.copy_blocks(cow_src, cow_dst)
            base = active

            def body(carry, j):
                cache, final = carry
                tok = tok_seq[j]
                pos = jnp.minimum(pos0 + j, view_len - 1)
                lane = base & (j < count)
                cache = cache.with_active(lane)
                cache, logits = self._step(params, cache, tok, pos,
                                           extras)
                final = jnp.where((j == count - 1)[:, None], logits,
                                  final)
                return (cache, final), None

            zeros = jnp.zeros((pos0.shape[0], V), jnp.float32)
            (cache, final), _ = jax.lax.scan(body, (cache, zeros),
                                             jnp.arange(C))
            return cache, final, greedy_token(final).astype(jnp.int32)

        _prefill_pure = _prefill_body if self._mp == 1 else \
            self._mpmod.wrap_spmd(_prefill_body, self._mesh2d)

        def _prefill_raw(params, cache, tok_seq, pos0, count, active,
                         cow_src, cow_dst, extras):
            self._prefill_traces += 1
            profiler.count_trace(f"serve:{name}:prefill")
            return _prefill_pure(params, cache, tok_seq, pos0, count,
                                 active, cow_src, cow_dst, extras)

        self._prefill_pure = _prefill_pure
        self._prefill_jit = jax.jit(_prefill_raw, donate_argnums=donate)
        self._donate = donate
        # Profiler contract (generalizing the decode_compiles == 1
        # guard): every dispatch is fingerprinted, so a shape/dtype drift
        # is counted in recompiles_total{program} and BLAMED by argument
        # instead of silently recompiling. HOROVOD_PROFILER_COST=1
        # additionally captures the compiled cost analysis per phase
        # (one extra compile each, through the pure twin — opt-in here,
        # unlike the free fingerprint; same parser as ProfiledStep).
        self._capture_cost = profiler._cost_capture_enabled(default=False)
        self._cost_captured: set = set()
        # Descriptor memo for the one heavy, engine-pinned dispatch arg:
        # params is the SAME object on every dispatch, so its pytree
        # descriptor (hundreds of leaves) is computed once, not per token.
        self._params_desc: Optional[Tuple[Any, str]] = None

    # ------------------------------------------------------------------
    # family extras (T5 cross-attention side state)
    # ------------------------------------------------------------------

    def _init_extras(self, max_src_len: Optional[int]):
        if self.family.name != "t5":
            self._max_src_len = None
            return None
        cfg = self.cfg
        self._max_src_len = int(max_src_len or self.max_len)
        H, hd = cfg.num_heads, cfg.head_dim
        cross = {i: {"k": jnp.zeros((self.slots, self._max_src_len, H, hd),
                                    cfg.dtype),
                     "v": jnp.zeros((self.slots, self._max_src_len, H, hd),
                                    cfg.dtype)}
                 for i in range(cfg.num_decoder_layers)}
        return {"cross": cross,
                "src_mask": jnp.zeros((self.slots, self._max_src_len),
                                      bool),
                "dec_bias": t5_decoder_bias(cfg, self.params,
                                            self.view_len)}

    def _admit_extras(self, slot: int, req: Request) -> None:
        """T5: run the encoder once for this request and scatter its
        cross K/V + source mask into the slot's rows."""
        if self.family.name != "t5":
            return
        cfg = self.cfg
        src = req.src.reshape(1, -1)
        pad = np.full((1, self._max_src_len - src.shape[1]), cfg.pad_id,
                      np.int32)
        src = jnp.asarray(np.concatenate([src, pad], axis=1))
        mask = src != cfg.pad_id
        cross = t5_encode(self.model, cfg, self.params, src, mask)
        ex = self._extras
        for i, row in enumerate(cross):
            ex["cross"][i] = {
                "k": ex["cross"][i]["k"].at[slot].set(row["k"][0]),
                "v": ex["cross"][i]["v"].at[slot].set(row["v"][0])}
        ex["src_mask"] = ex["src_mask"].at[slot].set(mask[0])

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def submit(self, prompt=None, max_new_tokens: int = 16, **kw) -> Request:
        """Enqueue one request; returns immediately with a handle whose
        ``result()`` blocks for the tokens. Over-long and malformed
        requests are rejected here, a full queue rejects with
        backpressure — the status/reason is always on the handle.

        ``prefill_only=True`` asks for the migration half-request: the
        engine prefills the prompt into its pool, exports the KV as
        fp32 host arrays on ``req.kv_export``, and finishes
        DONE/``"prefilled"`` without generating — the decode side
        grafts via :meth:`admit_prefilled`."""
        prefill_only = bool(kw.pop("prefill_only", False))
        if prefill_only and self.family.name == "t5":
            req = Request(prompt if prompt is not None else [],
                          max_new_tokens, **kw)
            req._finish(RequestStatus.REJECTED,
                        "prefill_only is not supported for t5 "
                        "(decoder KV depends on the per-request "
                        "encoder output)")
            return self._count_reject(req)
        if prefill_only and self._mp > 1:
            req = Request(prompt if prompt is not None else [0],
                          max_new_tokens, **kw)
            req._finish(RequestStatus.REJECTED,
                        "KV export from a tensor-parallel engine is "
                        "not implemented")
            return self._count_reject(req)
        if self.role == "prefill" and not prefill_only:
            # Retryable: the dispatcher mis-routed — a decode/both
            # replica can serve this request unchanged.
            req = Request(prompt if prompt is not None else [0],
                          max_new_tokens, **kw)
            req.retryable = True
            req._finish(RequestStatus.REJECTED,
                        "prefill-role engine accepts only "
                        "prefill_only requests")
            return self._count_reject(req)
        if prefill_only and self.role == "decode":
            req = Request(prompt if prompt is not None else [0],
                          max_new_tokens, **kw)
            req.retryable = True
            req._finish(RequestStatus.REJECTED,
                        "decode-role engine does not prefill")
            return self._count_reject(req)
        src = kw.get("src")
        if self.family.name == "t5":
            if src is None:
                req = Request(prompt if prompt is not None else [],
                              max_new_tokens, **kw)
                req._finish(RequestStatus.REJECTED,
                            "t5 requests need src= (encoder tokens)")
                return self._count_reject(req)
            if prompt is None or np.asarray(prompt).size == 0:
                kw_prompt = [self.cfg.pad_id]    # T5: pad doubles as BOS
            else:
                kw_prompt = prompt
            req = Request(kw_prompt, max_new_tokens, **kw)
            if req.src.size > (self._max_src_len or 0):
                req._finish(RequestStatus.REJECTED,
                            f"src length {req.src.size} exceeds "
                            f"max_src_len={self._max_src_len}")
                return self._count_reject(req)
        else:
            if prompt is None or np.asarray(prompt).size == 0:
                req = Request([0], max_new_tokens, **kw)
                req._finish(RequestStatus.REJECTED,
                            "decoder-only requests need a non-empty "
                            "prompt")
                return self._count_reject(req)
            req = Request(prompt, max_new_tokens, **kw)
        req.prefill_only = prefill_only
        if req.max_new_tokens < 1:
            req._finish(RequestStatus.REJECTED,
                        "max_new_tokens must be >= 1")
            return self._count_reject(req)
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_len:
            req._finish(RequestStatus.REJECTED,
                        f"prompt {len(req.prompt)} + {req.max_new_tokens} "
                        f"new tokens exceeds max_len={self.max_len}")
            return self._count_reject(req)
        need = self.manager.blocks_for(total)
        if need > self.manager.capacity:
            # Must reject NOW: _admit would requeue it forever (its
            # worst case can never be reserved), head-of-line blocking
            # every request behind it.
            req._finish(RequestStatus.REJECTED,
                        f"request needs {need} KV blocks but the pool "
                        f"holds {self.manager.capacity}")
            return self._count_reject(req)
        if req.temperature < 0:
            req._finish(RequestStatus.REJECTED,
                        f"temperature must be >= 0, got "
                        f"{req.temperature}")
            return self._count_reject(req)
        if req.top_k is not None and not \
                1 <= req.top_k <= self.cfg.vocab_size:
            req._finish(RequestStatus.REJECTED,
                        f"top_k must be in [1, vocab_size="
                        f"{self.cfg.vocab_size}], got {req.top_k}")
            return self._count_reject(req)
        if self.failed or self._stop.is_set():
            req.retryable = True
            req._finish(RequestStatus.REJECTED, "engine not serving")
            return self._count_reject(req)
        if self._draining:
            req.retryable = True
            req._finish(RequestStatus.REJECTED,
                        "engine draining; not accepting new requests")
            return self._count_reject(req)
        # Attach the terminal counter BEFORE enqueueing: the serving
        # loop can pop and expire a zero-deadline request in the gap,
        # and every terminal transition after acceptance — done,
        # expired, cancelled, failed, queue rejections — must land in
        # serve_requests_total so {status} sums back to {submitted}.
        req._on_terminal = self._request_terminal
        self.queue.submit(req)
        if req.status == RequestStatus.REJECTED:
            # The callback already counted the rejection; keep only the
            # timeline event (no double increment).
            metrics.event("serve_reject", engine=self.name,
                          request=req.id, reason=req.reason)
            return req
        metrics.counter("serve_requests_total", engine=self.name,
                        status="submitted").inc()
        self._work.set()
        return req

    def _request_terminal(self, req: Request) -> None:
        metrics.counter("serve_requests_total",
                        engine=req.served_by or self.name,
                        status=req.status.value).inc()

    def can_serve(self, req: Request) -> bool:
        """Would THIS engine's geometry accept ``req``? Engines in a
        dispatch group may differ (max_len, pool size, source window) —
        failover adoption must re-check against the adopter, not trust
        the dead engine's validation."""
        if self.failed or self._stop.is_set() or self._draining:
            return False
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_len or req.max_new_tokens < 1:
            return False
        if self.manager.blocks_for(total) > self.manager.capacity:
            return False
        if self.family.name == "t5":
            if req.src is None or req.src.size > (self._max_src_len or 0):
                return False
        if len(req.prompt) == 0:        # every family feeds prompt[0]
            return False
        if req.top_k is not None and not \
                1 <= req.top_k <= self.cfg.vocab_size:
            return False
        return True

    def adopt(self, req: Request) -> bool:
        """Failover path: enqueue an EXISTING request (same handle the
        caller holds) if this engine can serve it and has queue room;
        never finalizes the request on refusal, so the dispatcher can
        try the next survivor."""
        if not self.can_serve(req):
            return False
        if not self.queue.try_submit(req):
            return False
        metrics.counter("serve_requests_total", engine=self.name,
                        status="adopted").inc()
        self._work.set()
        return True

    def _count_reject(self, req: Request) -> Request:
        metrics.counter("serve_requests_total", engine=self.name,
                        status="rejected").inc()
        metrics.event("serve_reject", engine=self.name, request=req.id,
                      reason=req.reason)
        return req

    # ------------------------------------------------------------------
    # KV migration (serving/disagg.py rides these)
    # ------------------------------------------------------------------

    def export_kv(self, slot: int,
                  n_tokens: int) -> Tuple[np.ndarray, np.ndarray]:
        """Token-major fp32 ``(L, n_tokens, Hkv, hd)`` K/V snapshot of
        the slot's first ``n_tokens`` positions, dequantized through
        the pool's own scales. Token-major on purpose: block geometry
        is a LOCAL pool decision, so the wire never carries it and the
        two sides of a migration may disagree on ``block_size``."""
        if self._mp > 1:
            raise NotImplementedError(
                "KV export from a tensor-parallel engine is not "
                "implemented")
        blocks = self.manager.prompt_blocks(slot, n_tokens)
        k, v = self._cache.export_blocks(blocks)
        L, nb, bs, H, hd = k.shape
        k = k.reshape(L, nb * bs, H, hd)[:, :n_tokens]
        v = v.reshape(L, nb * bs, H, hd)[:, :n_tokens]
        return np.ascontiguousarray(k), np.ascontiguousarray(v)

    def admit_prefilled(self, prompt, max_new_tokens: int, k, v,
                        **kw) -> Request:
        """Graft a migrated prompt's KV into the local pool and enter
        decode directly — no queue, no re-prefill. ``k``/``v`` are the
        fp32 token-major arrays :meth:`export_kv` produced (already
        wire-decoded). The slot starts at ``n_fed = len(prompt) - 1``:
        the LAST prompt token is re-fed through the normal decode step
        (exactly the capped full-prompt prefix-match path), so the
        first token commits here — TTFT observed where the token is
        produced, the migrated prompt registered into THIS replica's
        radix index, and ``decode_compiles == 1`` untouched because a
        graft is host bookkeeping between dispatches.

        Pool pressure rejects with ``retryable=True`` so the caller
        can fall back to re-prefilling on a survivor; geometry
        mismatches raise (a wrong-model graft must never be silently
        decoded)."""
        if self.family.name == "t5":
            raise NotImplementedError(
                "KV migration is not supported for t5 (decoder KV "
                "depends on the per-request encoder output)")
        if self._mp > 1:
            raise NotImplementedError(
                "KV graft into a tensor-parallel engine is not "
                "implemented")
        if self.role == "prefill":
            raise ValueError(
                "prefill-role engine cannot accept KV grafts; route "
                "grafts to a decode or both replica")
        kw.pop("prefill_only", None)
        k = np.asarray(k, np.float32)
        v = np.asarray(v, np.float32)
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        layers = self.family.num_layers(self.cfg)
        H = self.family.kv_heads(self.cfg)
        hd = self.family.head_dim(self.cfg)
        want = (layers, len(prompt), H, hd)
        if k.shape != want or v.shape != want:
            raise ValueError(
                f"migrated KV shape {k.shape}/{v.shape} does not "
                f"match this engine's geometry {want} "
                f"(layers, prompt_tokens, kv_heads, head_dim)")
        req = Request(prompt, max_new_tokens, **kw)
        req.prefill_only = False
        if len(prompt) == 0:
            req._finish(RequestStatus.REJECTED,
                        "grafts need a non-empty prompt")
            return self._count_reject(req)
        if req.max_new_tokens < 1:
            req._finish(RequestStatus.REJECTED,
                        "max_new_tokens must be >= 1")
            return self._count_reject(req)
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_len:
            req._finish(RequestStatus.REJECTED,
                        f"prompt {len(req.prompt)} + "
                        f"{req.max_new_tokens} new tokens exceeds "
                        f"max_len={self.max_len}")
            return self._count_reject(req)
        if self.manager.blocks_for(total) > self.manager.capacity:
            req._finish(RequestStatus.REJECTED,
                        f"request needs "
                        f"{self.manager.blocks_for(total)} KV blocks "
                        f"but the pool holds {self.manager.capacity}")
            return self._count_reject(req)
        if req.temperature < 0 or (req.top_k is not None and not
                                   1 <= req.top_k <= self.cfg.vocab_size):
            req._finish(RequestStatus.REJECTED,
                        "bad sampling parameters for graft")
            return self._count_reject(req)
        req._on_terminal = self._request_terminal
        with self._lock:
            if self.failed or self._stop.is_set() or self._draining:
                req.retryable = True
                req._finish(RequestStatus.REJECTED, "engine not serving")
                return self._count_reject(req)
            if self._slot_pool.free_count == 0 or \
                    not self.manager.can_admit(total, 0, []):
                # A busy decode pool is a transient: the dispatcher
                # retries another decode replica or falls back to a
                # full re-prefill on a survivor.
                req.retryable = True
                req._finish(RequestStatus.REJECTED,
                            "no free slot/blocks for graft")
                return self._count_reject(req)
            metrics.counter("serve_requests_total", engine=self.name,
                            status="submitted").inc()
            if not req.start_running():
                return req
            now = time.monotonic()
            slot = self._slot_pool.acquire()
            self.manager.admit(slot, total, 0, [])
            try:
                blocks = self.manager.map_prefix_blocks(
                    slot, len(prompt))
                bs = self.block_size
                nb = len(blocks)
                pad = nb * bs - len(prompt)
                if pad:
                    zk = np.zeros((layers, pad, H, hd), np.float32)
                    k = np.concatenate([k, zk], axis=1)
                    v = np.concatenate([v, zk], axis=1)
                self._cache = self._cache.import_blocks(
                    blocks,
                    k.reshape(layers, nb, bs, H, hd),
                    v.reshape(layers, nb, bs, H, hd))
            except Exception:
                self.manager.release(slot)
                self._slot_pool.release(slot)
                raise
            span = tracing.mint_span("serve_request", tensor=req.id,
                                     traced=True)
            st = _SlotState(req, slot, span)
            st.n_fed = len(prompt) - 1
            self._states[slot] = st
            req.t_admit = now
            req.served_by = self.name
            req.prefix_tokens = 0
            key = tuple(int(t) for t in req.prompt[:self.block_size])
            self._overlap_total += 1
            if key in self._overlap_seen:
                self._overlap_hits += 1
            elif len(self._overlap_seen) < 8192:
                self._overlap_seen.add(key)
            self._graft_admissions += 1
            metrics.counter("serve_kv_grafts_total",
                            engine=self.name).inc()
            metrics.histogram("serve_queue_wait_seconds",
                              engine=self.name).observe(req.queue_wait)
            metrics.event("serve_kv_graft", engine=self.name,
                          request=req.id, slot=slot,
                          prompt_len=len(req.prompt), op_id=span.op_id)
            if req.trace is not None and reqtrace.enabled():
                reqtrace.instant("KV_GRAFT", req.trace,
                                 engine=self.name, request=req.id,
                                 slot=slot, tokens=len(prompt))
            self._update_gauges()
        self._work.set()
        return req

    # ------------------------------------------------------------------
    # one engine iteration (host bookkeeping + one device dispatch)
    # ------------------------------------------------------------------

    def step_once(self) -> int:
        """Evict, admit, advance every occupied lane one unit of work
        (one decode token, or one prefill chunk). Returns the number of
        lanes that advanced — 0 means idle."""
        with self._lock, tracing.span("engine.step", step=self.step_count):
            now = time.monotonic()
            with self._phase("sweep"):
                self._sweep(now)
            with self._phase("admit"):
                self._admit(now)
            lanes = sorted(self._states.items())
            if not lanes:
                self._update_gauges()
                return 0
            prefill = [(s, st) for s, st in lanes
                       if st.n_fed < len(st.request.prompt)]
            wants_chunk = self.prefill_chunk > 1 and any(
                len(st.request.prompt) - st.n_fed > 1
                for _, st in prefill)
            # Alternate chunked prefill with decode: a chunk freezes the
            # decode lanes, and under a sustained stream of long prompts
            # "prefill whenever someone needs it" would freeze them
            # FOREVER. Guaranteeing a decode dispatch between chunks
            # bounds the added TPOT at one chunk's latency. (Pure-
            # prefill states — nobody decoding — chunk back-to-back.)
            only_prefill = len(prefill) == len(lanes)
            if wants_chunk and (only_prefill or not self._last_prefill):
                self._run_prefill(prefill)
                self._last_prefill = True
            else:
                self._run_decode(lanes)
                self._last_prefill = False
            with self._phase("sweep"):
                self._sweep(time.monotonic())
                self._update_gauges()
            self.step_count += 1
            return len(lanes)

    def _phase(self, phase: str):
        """One phase of ``step_once``: the span ``hvd:engine.<phase>`` and
        its host seconds in ``serve_step_phase_seconds_total``."""
        return tracing.timed("engine." + phase, "serve_step_phase",
                             self.step_count, engine=self.name)

    def _sweep(self, now: float) -> None:
        """Finish lanes that went terminal (deadline, cancel) and free
        the slots/blocks of every terminal lane."""
        for slot in list(self._states):
            st = self._states[slot]
            req = st.request
            if not req.status.terminal and req.expired(now):
                req._finish(RequestStatus.EXPIRED,
                            "deadline passed mid-generation")
                # A mid-flight deadline breach is the serving analogue of
                # a collective stall: under HOROVOD_PROFILE_ON_STALL=1
                # capture a bounded device trace of the slow window.
                profiler.maybe_trigger(f"serve_deadline_{req.id}")
            if req._cancel_requested and not req.status.terminal:
                req._finish(RequestStatus.CANCELLED, req.reason)
            if req.status.terminal:
                self._evict(slot)

    def _evict(self, slot: int) -> None:
        st = self._states.pop(slot)
        self.manager.release(slot)
        self._slot_pool.release(slot)
        req = st.request
        if req.tpot is not None:
            metrics.histogram("serve_tpot_seconds",
                              buckets=metrics.SERVE_LATENCY_BUCKETS,
                              engine=self.name).observe(req.tpot)
        metrics.counter("serve_tokens_generated_total",
                        engine=self.name).inc(len(req.tokens))
        metrics.event("serve_finish", engine=self.name, request=req.id,
                      status=req.status.value, generated=len(req.tokens),
                      op_id=st.span.op_id)

    def _admit(self, now: float) -> None:
        while self._slot_pool.free_count > 0:
            req = self.queue.pop_ready(now)
            if req is None:
                return
            total = len(req.prompt) + req.max_new_tokens
            # Peek the prefix index BEFORE the admission check: a hit
            # shrinks the reservation to the unshared tail, so a request
            # the worst-case check would park can often be admitted
            # immediately. Safe as a peek-then-admit pair because every
            # manager mutation runs under the engine lock we hold.
            n_matched, attach = self.manager.match_prefix(req.prompt) \
                if self.prefix_enabled else (0, [])
            if not self.manager.can_admit(total, n_matched, attach):
                # Head-of-line waits for blocks; FCFS order preserved
                # (the heap keys on the original sequence number).
                self.queue.requeue(req)
                return
            if not req.start_running():
                continue    # cancelled in the pop->admit window
            slot = self._slot_pool.acquire()
            self.manager.admit(slot, total, n_matched, attach)
            span = tracing.mint_span("serve_request", tensor=req.id,
                                     traced=True)
            st = _SlotState(req, slot, span)
            # The matched preamble is already in the pool: the slot
            # starts with those tokens fed and only the divergent tail
            # is ever prefilled. (match_prefix caps at prompt_len - 1 —
            # at least one token must be re-fed to produce logits.)
            st.n_fed = n_matched
            self._states[slot] = st
            req.t_admit = now
            req.served_by = self.name
            req.prefix_tokens = n_matched
            if self.family.name != "t5":
                key = tuple(int(t) for t in req.prompt[:self.block_size])
                self._overlap_total += 1
                if key in self._overlap_seen:
                    self._overlap_hits += 1
                elif len(self._overlap_seen) < 8192:
                    self._overlap_seen.add(key)
            metrics.histogram("serve_queue_wait_seconds",
                              engine=self.name).observe(req.queue_wait)
            self._admit_extras(slot, req)
            metrics.event("serve_admit", engine=self.name, request=req.id,
                          slot=slot, prompt_len=len(req.prompt),
                          op_id=span.op_id)
            if req.trace is not None and reqtrace.enabled():
                qw = max(0.0, float(req.queue_wait or 0.0))
                reqtrace.emit("QUEUE", req.trace, time.time() - qw, qw,
                              engine=self.name, request=req.id)
                reqtrace.instant("ADMIT", req.trace, engine=self.name,
                                 request=req.id, slot=slot,
                                 prefix_tokens=n_matched)
            if n_matched > 0:
                metrics.counter("prefix_tokens_reused_total",
                                engine=self.name).inc(n_matched)
                metrics.event("serve_prefix_hit", engine=self.name,
                              request=req.id, slot=slot,
                              tokens=n_matched, op_id=span.op_id)

    # -- device dispatches ----------------------------------------------

    #: dispatch argument names per phase — the recompile detector blames
    #: by name, so a drifting signature reads "tok: int32[8] -> int32[16]"
    _ARGNAMES = {
        "decode": ("params", "cache", "tok_seq", "pos0", "counts",
                   "active", "cow_src", "cow_dst", "extras"),
        "prefill": ("params", "cache", "tok_seq", "pos0", "count",
                    "active", "cow_src", "cow_dst", "extras"),
    }

    def _dispatch(self, phase: str, fn, *args):
        """Run one jitted call under watchdog + timeline coverage; the
        pending-collective entry makes a wedged decode step a named
        stall report instead of a silent hang."""
        prog = f"serve:{self.name}:{phase}"
        names = self._ARGNAMES.get(phase)
        if names:
            sig = {}
            for n, a in zip(names, args):
                if n == "params":
                    hit = self._params_desc
                    if hit is None or hit[0] is not a:
                        hit = self._params_desc = (a, profiler.describe(a))
                    sig[n] = hit[1]
                else:
                    sig[n] = profiler.describe(a)
            profiler.note_trace(prog, sig, kind="serving")
            if self._capture_cost and phase not in self._cost_captured:
                self._cost_captured.add(phase)
                self._register_cost(prog, phase, args)
        tok = metrics.collective_begin(
            "serve_step", name=f"{self.name}:{phase}:{self.step_count}")
        t0 = time.perf_counter()
        try:
            with tracing.phase(self._span, phase.upper(),
                               category="serving", step=self.step_count):
                out = fn(*args)
                # Force completion INSIDE the watchdog window: jax
                # dispatch is async, and an unforced wedge would look
                # like instant success here and hang at the next use.
                out = jax.tree_util.tree_map(
                    lambda a: a.block_until_ready()
                    if hasattr(a, "block_until_ready") else a, out)
        finally:
            metrics.collective_end(tok)
        dt = time.perf_counter() - t0
        metrics.histogram("serve_step_seconds", engine=self.name,
                          phase=phase).observe(dt)
        # The dispatch already blocks for the watchdog, so this timing is
        # an honest device step — it feeds the program's roofline gauges
        # (program_hfu / hbm_bandwidth_utilization) for free.
        profiler.observe_step(prog, dt)
        return out

    def _register_cost(self, prog: str, phase: str, args) -> None:
        """Capture the phase program's cost analysis through its PURE
        twin — lowering the counting wrapper would bump the trace
        counters and break the ``decode_compiles == 1`` contract."""
        pure = self._decode_pure if phase == "decode" else \
            self._prefill_pure
        try:
            compiled = jax.jit(pure, donate_argnums=self._donate).lower(
                *args).compile()
            profiler.record_cost(prog, compiled, kind="serving",
                                 mp_degree=self._mp)
        except Exception:
            metrics.logger.debug("serve cost capture failed for %s",
                                 prog, exc_info=True)

    def _dev(self, x):
        """Host step vector -> the dispatch layout: plain device array
        replicated, or mp-stacked (every row identical — the per-step
        inputs are computed in host lockstep on every process)."""
        if self._mp == 1:
            return jnp.asarray(x)
        return self._mpmod.mp_broadcast(np.asarray(x), self._mesh2d)

    def _host(self, x) -> np.ndarray:
        """Device output -> host numpy: one row of the mp stack (the tp
        steps return replicated-content outputs — gathered logits and
        greedy picks are identical on every rank)."""
        if self._mp == 1:
            return np.asarray(x)
        return self._mpmod.mp_fetch(x)

    def _device_table(self):
        """The block table in dispatch layout. A dirty host table comes
        back 2-D and needs the mp broadcast; a clean one is the adopted
        jit-output mirror, already stacked."""
        t = self.manager.device_table()
        if self._mp > 1 and t.ndim == 2:
            t = self._mpmod.mp_broadcast(np.asarray(t), self._mesh2d)
        return t

    def _emit_decode_spans(self, lanes: List[Tuple[int, _SlotState]],
                           t0_wall: float, dur_s: float) -> None:
        """One DECODE span per traced lane, sampled every
        ``HOROVOD_REQUEST_TRACE_DECODE_EVERY`` steps (the first step of a
        lane always emits) so a long generation costs O(tokens/N) spans."""
        try:
            from horovod_tpu.config import get_config
            every = max(1, int(get_config().request_trace_decode_every))
        except Exception:
            every = 16
        for slot, st in lanes:
            if st.request.trace is None:
                continue
            st.decode_steps += 1
            if (st.decode_steps - 1) % every == 0:
                reqtrace.emit("DECODE", st.request.trace, t0_wall, dur_s,
                              engine=self.name, request=st.request.id,
                              slot=slot, step=st.decode_steps,
                              sampled_every=every)

    def _run_decode(self, lanes: List[Tuple[int, _SlotState]]) -> None:
        with self._phase("build"):
            tok_seq, counts, proposed, args = self._build_decode(lanes)
        _rt_t0 = time.time()
        with self._phase("dispatch"):
            cache, first, greedy = self._dispatch(
                "decode", self._decode_jit, self.params, *args,
                self._extras)
        if reqtrace.enabled():
            self._emit_decode_spans(lanes, _rt_t0, time.time() - _rt_t0)
        self._cache = cache
        self.manager.set_device_mirror(cache.table)
        with self._phase("readback"):
            greedy_np = self._host(greedy)               # (K, slots)
            logits_np = self._pull_logits_if_sampling(lanes, first)
        metrics.counter("serve_steps_total", engine=self.name,
                        phase="decode").inc()
        with self._phase("commit"):
            self._commit_decode(lanes, tok_seq, counts, proposed,
                                greedy_np, logits_np)

    def _build_decode(self, lanes: List[Tuple[int, _SlotState]]):
        """The decode dispatch's host arrays, and their device copies in
        argument order after ``params``."""
        K = self.spec_k + 1
        tok_seq = np.zeros((K, self.slots), np.int32)
        pos0 = np.zeros(self.slots, np.int32)
        counts = np.zeros(self.slots, np.int32)
        act = np.zeros(self.slots, bool)
        cow_src = np.full(self.slots, TRASH_BLOCK, np.int32)
        cow_dst = np.full(self.slots, TRASH_BLOCK, np.int32)
        proposed = 0
        for slot, st in lanes:
            req = st.request
            p = req.prompt
            nf = st.n_fed
            tok_seq[0, slot] = p[nf] if nf < len(p) else \
                req.tokens[nf - len(p)]
            pos0[slot] = nf
            act[slot] = True
            c = 1
            # Draft only once the lane is generating (every fed token
            # from here on is model output) and only for greedy lanes:
            # sampled tokens can't be verified against a greedy chain.
            if K > 1 and req.temperature == 0 and nf >= len(p) - 1:
                total = len(p) + req.max_new_tokens
                # Feeding c tokens writes positions nf..nf+c-1 and can
                # commit through position nf+c — cap so the chain never
                # runs past the request's last token.
                drafts = self._propose(req)[:max(0, total - 1 - nf - 1)]
                for j, d in enumerate(drafts):
                    tok_seq[1 + j, slot] = d
                c = 1 + len(drafts)
                proposed += len(drafts)
            counts[slot] = c
            for q in range(nf, nf + c):
                r = self.manager.ensure_writable(slot, q)
                if r is not None:
                    cow_src[slot], cow_dst[slot] = r
                    if req.trace is not None and reqtrace.enabled():
                        reqtrace.instant("COW", req.trace,
                                         engine=self.name, request=req.id,
                                         slot=slot, pos=q, phase="decode")
        cache = self._cache.replace(table=self._device_table())
        return tok_seq, counts, proposed, (
            cache, self._dev(tok_seq), self._dev(pos0), self._dev(counts),
            self._dev(act), self._dev(cow_src), self._dev(cow_dst))

    def _commit_decode(self, lanes, tok_seq, counts, proposed, greedy_np,
                       logits_np) -> None:
        accepted = 0
        for slot, st in lanes:
            req = st.request
            p = req.prompt
            nf = st.n_fed
            c = int(counts[slot])
            if req.temperature > 0:
                st.n_fed += 1
                if nf >= len(p) - 1:
                    self._commit(st, slot, greedy_np[0], logits_np)
                continue
            # Verify chain: draft tok_seq[j] was fed on the model's
            # behalf — it stands iff it equals what the model actually
            # picked after the previous step (greedy[j-1]) and every
            # draft before it stood. v = length of the valid prefix.
            v = 1
            while v < c and tok_seq[v, slot] == greedy_np[v - 1, slot]:
                v += 1
            accepted += v - 1
            advanced = 0
            for j in range(v):
                advanced = j + 1
                if nf + j >= len(p) - 1:
                    if self._commit_token(st, slot,
                                          int(greedy_np[j, slot])):
                        break               # EOS/max mid-chain: stop
            st.n_fed += advanced
        if proposed:
            self._spec_proposed += proposed
            self._spec_accepted += accepted
            metrics.counter("spec_tokens_proposed_total",
                            engine=self.name).inc(proposed)
            metrics.counter("spec_tokens_accepted_total",
                            engine=self.name).inc(accepted)
            metrics.event("serve_spec_verify", engine=self.name,
                          proposed=proposed, accepted=accepted)

    def _run_prefill(self, lanes: List[Tuple[int, _SlotState]]) -> None:
        with self._phase("build"):
            count, args = self._build_prefill(lanes)
        _rt_t0 = time.time()
        with self._phase("dispatch"):
            cache, final, greedy = self._dispatch(
                "prefill", self._prefill_jit, self.params, *args,
                self._extras)
        if reqtrace.enabled():
            _rt_dur = time.time() - _rt_t0
            for slot, st in lanes:
                if st.request.trace is not None:
                    reqtrace.emit("PREFILL", st.request.trace, _rt_t0,
                                  _rt_dur, engine=self.name,
                                  request=st.request.id, slot=slot,
                                  tokens=int(count[slot]))
        self._cache = cache
        self.manager.set_device_mirror(cache.table)
        with self._phase("readback"):
            greedy_np = self._host(greedy)
            logits_np = self._pull_logits_if_sampling(lanes, final)
        metrics.counter("serve_steps_total", engine=self.name,
                        phase="prefill").inc()
        with self._phase("commit"):
            for slot, st in lanes:
                st.n_fed += int(count[slot])
                if st.n_fed >= len(st.request.prompt):
                    self._commit(st, slot, greedy_np, logits_np)

    def _build_prefill(self, lanes: List[Tuple[int, _SlotState]]):
        """The prefill dispatch's host arrays, and their device copies in
        argument order after ``params``."""
        C = self.prefill_chunk
        tok_seq = np.zeros((C, self.slots), np.int32)
        pos0 = np.zeros(self.slots, np.int32)
        count = np.zeros(self.slots, np.int32)
        act = np.zeros(self.slots, bool)
        cow_src = np.full(self.slots, TRASH_BLOCK, np.int32)
        cow_dst = np.full(self.slots, TRASH_BLOCK, np.int32)
        for slot, st in lanes:
            p = st.request.prompt
            c = min(C, len(p) - st.n_fed)
            tok_seq[:c, slot] = p[st.n_fed:st.n_fed + c]
            pos0[slot] = st.n_fed
            count[slot] = c
            act[slot] = True
            for q in range(st.n_fed, st.n_fed + c):
                r = self.manager.ensure_writable(slot, q)
                if r is not None:
                    cow_src[slot], cow_dst[slot] = r
                    if st.request.trace is not None and reqtrace.enabled():
                        reqtrace.instant("COW", st.request.trace,
                                         engine=self.name,
                                         request=st.request.id,
                                         slot=slot, pos=q, phase="prefill")
        cache = self._cache.replace(table=self._device_table())
        return count, (
            cache, self._dev(tok_seq), self._dev(pos0), self._dev(count),
            self._dev(act), self._dev(cow_src), self._dev(cow_dst))

    def _pull_logits_if_sampling(self, lanes, logits):
        """One bulk device->host transfer when ANY lane will host-sample
        this step; greedy-only steps never pay for logits at all, and
        sampling lanes share the single pull instead of one slice
        round-trip each."""
        if any(st.request.temperature > 0 for _, st in lanes):
            return self._host(logits).astype(np.float64)
        return None

    def _commit(self, st: _SlotState, slot: int, greedy_np,
                logits_np) -> None:
        req = st.request
        if req.temperature > 0:
            token = self._host_sample(req, logits_np[slot])
        else:
            token = int(greedy_np[slot])
        self._commit_token(st, slot, token)

    def _commit_token(self, st: _SlotState, slot: int,
                      token: int) -> bool:
        """Append one generated token; returns True when the request
        went terminal (EOS or max_new_tokens). On the FIRST token the
        prompt is fully written, so this is also where the slot's
        prompt chunks are published into the prefix index — published
        whole-prompt blocks are never written again (all later writes
        land at positions >= len(prompt))."""
        req = st.request
        first = req.t_first is None
        if first and getattr(req, "prefill_only", False):
            # Prefill-phase terminal: reaching the first-token point
            # means every prompt position is written, so snapshot the
            # KV for migration and finish WITHOUT committing — the
            # decode side re-feeds the LAST prompt token and produces
            # t0 itself (its own TTFT, its own prefix registration),
            # which is what keeps token parity and decode_compiles==1
            # on the engine that actually generates.
            if self.prefix_enabled:
                self.manager.register_prefix(slot, req.prompt)
            req.kv_export = self.export_kv(slot, len(req.prompt))
            self._prefill_exports += 1
            metrics.counter("serve_kv_exports_total",
                            engine=self.name).inc()
            metrics.event("serve_kv_export", engine=self.name,
                          request=req.id, tokens=len(req.prompt),
                          op_id=st.span.op_id)
            if req.trace is not None and reqtrace.enabled():
                reqtrace.instant("KV_EXPORT", req.trace,
                                 engine=self.name, request=req.id,
                                 tokens=len(req.prompt))
            req._finish(RequestStatus.DONE, "prefilled")
            return True
        req._commit(token)
        if first:
            metrics.histogram("serve_ttft_seconds",
                              buckets=metrics.SERVE_LATENCY_BUCKETS,
                              engine=self.name).observe(req.ttft)
            metrics.event("serve_first_token", engine=self.name,
                          request=req.id, op_id=st.span.op_id)
            if req.trace is not None and reqtrace.enabled():
                reqtrace.instant("FIRST_TOKEN", req.trace,
                                 engine=self.name, request=req.id,
                                 side="server", ttft_s=req.ttft)
            if self.prefix_enabled:
                self.manager.register_prefix(slot, req.prompt)
        if (req.eos_id is not None and token == req.eos_id) \
                or len(req.tokens) >= req.max_new_tokens:
            req._finish(RequestStatus.DONE)
            return True
        return False

    def _propose(self, req: Request) -> List[int]:
        """n-gram draft tokens for the speculative lane: find the most
        recent EARLIER occurrence of the context's current suffix
        (pattern lengths 3, then 2, then 1) in prompt + generated text
        and propose the ``spec_k`` tokens that followed it. Pure host
        lookup — no draft model, no extra device work; repetitive spans
        (templates, code, loops) verify at high acceptance, novel text
        simply proposes nothing. O(len(context) * k) per call."""
        hist = [int(t) for t in req.prompt] + [int(t) for t in req.tokens]
        n = len(hist)
        for m in (3, 2, 1):
            if n < m + 1:
                continue
            pat = hist[n - m:]
            for s in range(n - m - 1, -1, -1):
                if hist[s:s + m] == pat:
                    nxt = hist[s + m:s + m + self.spec_k]
                    if nxt:
                        return nxt
                    break
        return []

    @staticmethod
    def _host_sample(req: Request, row: np.ndarray) -> int:
        """Host-side temperature/top-k sampling (per-request numpy rng —
        seeded, so a resubmitted request replays identically)."""
        row = row / req.temperature
        if req.top_k is not None:
            kth = np.sort(row)[-req.top_k]
            row = np.where(row >= kth, row, -np.inf)
        row = row - row.max()
        p = np.exp(row)
        p /= p.sum()
        return int(req._rng.choice(len(row), p=p))

    # ------------------------------------------------------------------
    # drive modes
    # ------------------------------------------------------------------

    def run_until_idle(self, max_steps: int = 100_000) -> int:
        """Synchronous drive: step until no queued or running work is
        left (tests, batch jobs). Returns the number of iterations."""
        steps = 0
        while steps < max_steps:
            n = self.step_once()
            if n == 0 and self.queue.depth() == 0:
                return steps
            steps += 1
        raise RuntimeError(f"engine did not go idle in {max_steps} steps")

    def _on_config(self, env: str, old: Any, new: Any, ep: int) -> None:
        """Config-bus subscriber (confbus.py): live-retarget the engine
        knobs that are safe without a retrace. Prefix caching can turn
        OFF any time (admission just stops matching); it can turn ON
        only when the pool was BUILT with a radix index — otherwise the
        mutation applies fleet-wide but this engine stays off (logged),
        because the index must exist from construction."""
        if env == "HOROVOD_SERVE_PREFIX_CACHE":
            want = bool(new) and self.family.name != "t5"
            if want and self.manager.prefix is None:
                import logging
                logging.getLogger("horovod_tpu").warning(
                    "serve[%s]: HOROVOD_SERVE_PREFIX_CACHE=1 ignored: "
                    "pool was built without a prefix index; restart the "
                    "replica to enable prefix caching", self.name)
                return
            self.prefix_enabled = want

    def start(self) -> "InferenceEngine":
        """Background serving thread (the replica servers use this)."""
        if self._thread is not None:
            return self
        try:
            from horovod_tpu import confbus
            confbus.subscribe(self._on_config)
        except Exception:
            pass
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    n = self.step_once()
                except Exception as e:      # noqa: BLE001 — fail the lanes
                    self._fail(f"engine loop error: {e!r}")
                    return
                if n == 0:
                    self._work.wait(0.005)
                    self._work.clear()

        self._thread = threading.Thread(
            target=loop, name=f"hvd-serve-{self.name}", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._work.set()
        try:
            from horovod_tpu import confbus
            confbus.unsubscribe(self._on_config)
        except Exception:
            pass
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    def close(self, reason: str = "engine shut down") -> None:
        """Stop serving and resolve every outstanding request."""
        self.stop()
        with self._lock:
            self.queue.close(reason)
            for slot in list(self._states):
                st = self._states[slot]
                st.request._finish(RequestStatus.REJECTED, reason)
                self._evict(slot)
            self._update_gauges()

    def drain(self, timeout: float = 60.0) -> bool:
        """Graceful drain: finish everything in flight and queued while
        REJECTING new submissions (reason "engine draining"); True when
        the engine emptied in time. Draining is one-way — the natural
        next call is ``close()``."""
        self._draining = True
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                busy = bool(self._states) or self.queue.depth() > 0
            if not busy:
                return True
            if self._thread is None:
                self.step_once()
            else:
                time.sleep(0.01)
        return False

    def _fail(self, reason: str) -> None:
        self.failed = reason
        metrics.event("serve_engine_failed", engine=self.name,
                      reason=reason)
        orphans = []
        with self._lock:
            for slot in list(self._states):
                st = self._states[slot]
                st.request.retryable = True
                st.request._finish(RequestStatus.FAILED, reason)
                self._evict(slot)
            # Engine death is FAILED (retryable elsewhere), not a
            # client-error REJECTED: the replica spool respools FAILED
            # claims for survivors, and the dispatcher re-enqueues the
            # same handles via on_fail.
            orphans = [r for r in self.queue.drain()
                       if not r.status.terminal]
            self.queue.close(reason)
            if self.on_fail is None:
                for r in orphans:
                    r.retryable = True
                    r._finish(RequestStatus.FAILED, reason)
                orphans = []
            self._update_gauges()
        if orphans:
            try:
                self.on_fail(self, orphans)
            except Exception:
                for r in orphans:
                    r.retryable = True
                    r._finish(RequestStatus.FAILED, reason)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    @property
    def alive(self) -> bool:
        return self.failed is None and not self._stop.is_set()

    @property
    def decode_compiles(self) -> int:
        """How many times the decode step was TRACED (== compiled): the
        continuous-batching contract is that this stays at 1 however
        requests churn."""
        return self._decode_traces

    @property
    def prefill_compiles(self) -> int:
        return self._prefill_traces

    def load(self) -> int:
        """Dispatch weight: queued + running requests."""
        with self._lock:
            return self.queue.depth() + len(self._states)

    def _update_gauges(self) -> None:
        metrics.gauge("serve_slots_active", engine=self.name).set(
            len(self._states))
        metrics.gauge("serve_queue_depth", engine=self.name).set(
            self.queue.depth())
        metrics.gauge("serve_blocks_in_use", engine=self.name).set(
            self.manager.blocks_in_use)
        metrics.gauge("serve_blocks_peak", engine=self.name).set(
            self.manager.peak_blocks_in_use)
        # KV-pool occupancy in BYTES: the memory-accounting view the
        # profiler's doctor reads next to program_peak_hbm_bytes —
        # blocks_in_use says "how full", this says "how much HBM that is".
        bpb = self._bytes_per_block
        metrics.gauge("serve_kv_pool_bytes_in_use", engine=self.name).set(
            self.manager.blocks_in_use * bpb)
        metrics.gauge("serve_kv_pool_bytes_capacity",
                      engine=self.name).set(self._pool_bytes)
        # The doctor's sharding check reads these two next to the pool
        # gauges: "rejecting with quant already on" + "replicated
        # params" together say the fix is a mesh, not a knob.
        metrics.gauge("serve_kv_quant_enabled", engine=self.name).set(
            1 if self.kv_quant else 0)
        metrics.gauge("serve_mp_degree", engine=self.name).set(self._mp)
        # Role + capacity gauges: the doctor's _check_roles and hvd.top
        # read these to see the two pools — slots_total alongside
        # slots_active gives saturation without config access.
        metrics.gauge("serve_slots_total", engine=self.name).set(
            self.slots)
        metrics.gauge("serve_role", engine=self.name,
                      role=self.role).set(1)
        if self._overlap_total:
            metrics.gauge("serve_prompt_overlap_rate",
                          engine=self.name).set(
                self._overlap_hits / self._overlap_total)
        ps = self.manager.prefix_stats()
        if self.prefix_enabled:
            metrics.gauge("prefix_cache_hit_rate", engine=self.name).set(
                ps["hit_rate"])
            metrics.gauge("prefix_cache_hit_rate", engine=self.name,
                          scope="local").set(ps["hit_rate"])
            metrics.gauge("prefix_cache_evictions", engine=self.name).set(
                ps["evictions"])
            metrics.gauge("kv_blocks_shared", engine=self.name).set(
                self.manager.shared_block_count())
        # Fleet-scope hit rate: a graft IS a prefix hit at fleet scope
        # (the prefill ran on another replica). Emitted even with the
        # local cache off and disagg off — a monolithic fleet's fleet
        # rate equals its local rate (grafts == 0), which is exactly
        # the baseline the doctor compares affinity routing against.
        fleet_den = ps["lookups"] + self._graft_admissions
        metrics.gauge("prefix_cache_hit_rate", engine=self.name,
                      scope="fleet").set(
            (ps["hits"] + self._graft_admissions) / fleet_den
            if fleet_den else 0.0)
        if self.spec_k > 0 and self._spec_proposed:
            metrics.gauge("spec_acceptance_rate", engine=self.name).set(
                self._spec_accepted / self._spec_proposed)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "engine": self.name, "alive": self.alive,
                "role": self.role,
                "kv_grafts": self._graft_admissions,
                "kv_exports": self._prefill_exports,
                "slots": self.slots, "active": len(self._states),
                "queued": self.queue.depth(),
                "steps": self.step_count,
                "decode_compiles": self._decode_traces,
                "prefill_compiles": self._prefill_traces,
                "blocks_in_use": self.manager.blocks_in_use,
                "blocks_peak": self.manager.peak_blocks_in_use,
                "blocks_capacity": self.manager.capacity,
                "dense_equivalent_tokens": self.slots * self.max_len,
                "kv_quant": self.kv_quant,
                "prefix_cache": self.prefix_enabled,
                "prefix": self.manager.prefix_stats(),
                "blocks_shared": self.manager.shared_block_count(),
                "spec_k": self.spec_k,
                "spec_proposed": self._spec_proposed,
                "spec_accepted": self._spec_accepted,
                "spec_acceptance": (self._spec_accepted /
                                    self._spec_proposed
                                    if self._spec_proposed else 0.0),
                "mesh": self._mesh_spec,
                "mp": self._mp,
                "param_bytes_per_rank": self._param_bytes,
                "kv_pool_bytes_per_rank": self._pool_bytes,
            }
