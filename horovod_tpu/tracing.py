"""Tracing: what the program says about its own work, in one place.

Three things live here, and nothing else in the package defines a span, a
scope or a span counter:

* **Profiler spans and scopes** — :func:`span` is a
  ``jax.profiler.TraceAnnotation`` named ``hvd:<name>`` (a host span on the
  profiler's clock, nested by thread; with no profiler session it is a flag
  check in C++, which is "tracing off"); :func:`scope` is a
  ``jax.named_scope`` for code under ``jit`` (operation metadata only: no
  operation is added or moved); :func:`timed` is a span that also adds its
  host seconds to a counter pair of ``metrics.registry``. :data:`NAMES` is
  the table of every name emitted: its layer, what it covers and which
  per-layer metric of the benchmark reads it (``tests/test_tracing_spans.py``
  fails on a name used but not listed; ``PERF.md`` and
  ``docs/OBSERVABILITY.md`` are written from it).
* **The sync manifest** — what a program handed to all-reduce for its
  gradients, counted while the program is traced, from static shapes
  (:func:`program`, :func:`sync_pass`, :func:`note_bucket`), and which
  leaves a finished pass of the same trace returned
  (:func:`mark_synced`, :func:`synced_as`), so that a second pass over
  them lowers nothing.
* **The routing manifest** — what the routed expert layers, the
  block-diffusion, causal and sliding-window attention calls and the latent
  attention of a program are shaped for, noted while the program is traced
  (:func:`note_routing`), and how the routing of one batch loaded the
  experts held (:func:`routing_load`).
* **The remat count** — how often a block's remat policy kept a residual
  that the flash forward named, while the program is traced
  (:func:`note_residual_saved`).
* **The set-up ledger** — one ``jax.monitoring`` listener, registered when
  this module is imported, that adds jax's own trace / lower / backend /
  cache-load seconds to ``jax_compile_seconds_total{phase,fun}``.
* **The scope table** — for a program built by ``hvd.spmd``, every
  instruction of its compiled text with the scopes of :data:`NAMES` it
  lies in, its direction and whether it is a container
  (:func:`scope_table`): the map from a device event's instruction name to
  a scope, which a reader of a device trace joins with its events. Made
  when it is asked for and not before, from what ``hvd.spmd`` noted while
  the program was traced (:func:`note_lowering`).

It also holds the older correlation layer for eager collectives:

Upstream Horovod's ``timeline.cc`` keys every NEGOTIATE / QUEUE / NCCL phase
event to the tensor being reduced, and because every rank logs the same
phases for the same tensor, merged per-rank timelines line up into one
cross-rank story. This module is that correlation layer for the TPU rebuild:

* :func:`mint_span` hands out a **monotone op-id** at collective enqueue time
  (``collective.py``). Negotiation enforces that every process issues the
  same eager collectives in the same order, so locally-minted ids agree
  across ranks without any extra wire traffic — rank 3's op #17 *is* rank
  5's op #17.
* The span travels through negotiation, fusion, dispatch, and completion;
  each layer emits timeline phase events (``NEGOTIATE`` / ``QUEUE`` /
  ``EXEC``) carrying ``op_id`` + ``process_set`` + ``tensor`` args, so
  ``trace_merge.py`` can compute per-collective arrival spread and straggler
  blame across rank shards.
* :func:`active_span` / :func:`current_span` expose the in-flight span to
  layers that cannot take it as an argument (the fusion planner runs inside
  the traced function body).

Span ids restart together with the negotiation history (`re-init`, elastic
re-mesh) — both count the same submission sequence.
"""

from __future__ import annotations

import re
import threading
import time
import weakref
from contextlib import contextmanager
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax

from horovod_tpu import metrics as _metrics

__all__ = ["Span", "mint_span", "current_span", "active_span",
           "reset_spans", "phase",
           "NAMES", "Name", "span", "scope", "timed", "current_scope",
           "program", "note_program", "sync_pass", "note_bucket",
           "mark_synced", "synced_as", "note_routing", "routing_load",
           "routing_bias_moved", "note_residual_saved",
           "ScopeRow", "scope_table", "note_lowering"]

_LOCK = threading.Lock()
_SEQ = 0
_TRACED_SEQ = 0
_TLS = threading.local()


class Span:
    """Identity of one collective operation, shared by every rank.

    ``op_id`` is the position in the (negotiation-ordered) submission
    sequence; ``process_set`` the set id the op ran on; ``tensor`` the
    user-facing name (``name=`` argument, or ``kind#op_id`` when unnamed).
    """

    __slots__ = ("op_id", "kind", "tensor", "process_set")

    def __init__(self, op_id: int, kind: str, tensor: str,
                 process_set: int = 0):
        self.op_id = op_id
        self.kind = kind
        self.tensor = tensor
        self.process_set = process_set

    def args(self) -> Dict[str, Any]:
        """Timeline-event args every phase of this op carries."""
        return {"op_id": self.op_id, "kind": self.kind,
                "tensor": self.tensor, "process_set": self.process_set}

    def __repr__(self) -> str:
        return (f"Span(op_id={self.op_id}, kind={self.kind!r}, "
                f"tensor={self.tensor!r}, process_set={self.process_set})")


def mint_span(kind: str, tensor: Optional[str] = None,
              process_set: int = 0, traced: bool = False) -> Span:
    """Mint the next span in the submission sequence (enqueue time).

    ``traced=True`` is for in-jit lowerings: those happen once per
    *compilation*, whose order is per-process (compile caches differ
    across ranks), so they draw from a separate NEGATIVE id sequence —
    never comparable cross-rank, never colliding with the
    negotiation-ordered eager ids trace_merge correlates."""
    global _SEQ, _TRACED_SEQ
    with _LOCK:
        if traced:
            _TRACED_SEQ -= 1
            op_id = _TRACED_SEQ
        else:
            _SEQ += 1
            op_id = _SEQ
    return Span(op_id, kind,
                tensor if tensor else f"{kind}#{op_id}", process_set)


def reset_spans() -> None:
    """Restart the op-id sequences (re-init / elastic re-mesh, alongside
    ``collective._reset_negotiation`` — ids and negotiation history count
    the same submission sequence and must restart together)."""
    global _SEQ, _TRACED_SEQ
    with _LOCK:
        _SEQ = 0
        _TRACED_SEQ = 0


def current_span() -> Optional[Span]:
    """The span of the collective currently being traced/dispatched on this
    thread, if any (what fusion reads to stamp its flush events)."""
    return getattr(_TLS, "span", None)


@contextmanager
def active_span(span: Optional[Span]):
    """Bind ``span`` as the thread's current span for the duration."""
    prev = getattr(_TLS, "span", None)
    _TLS.span = span
    try:
        yield span
    finally:
        _TLS.span = prev


@contextmanager
def phase(span: Optional[Span], name: str, category: str = "phase",
          **extra):
    """Emit a timeline complete-event for one phase of ``span``
    (``NEGOTIATE`` / ``QUEUE`` / ``EXEC``, mirroring upstream
    ``timeline.cc`` phase rows). No-op when no timeline is active; never
    raises into the dispatch hot path."""
    t = None
    try:
        from horovod_tpu import timeline as _tl
        t = _tl.get_timeline()
    except Exception:
        pass
    if t is None or span is None:
        yield
        return
    args = dict(span.args(), **extra)
    try:
        cm = t.activity(name, category=category, **args)
        cm.__enter__()
    except Exception:
        yield
        return
    try:
        yield
    finally:
        try:
            cm.__exit__(None, None, None)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# profiler spans and scopes
# ---------------------------------------------------------------------------

class Name(NamedTuple):
    """One row of :data:`NAMES`."""
    kind: str       # span | scope | kernel | counter | gauge
    layer: str      # the layer as PERF.md section 3 names it
    covers: str     # what the name stands for, in one line
    feeds: str      # the per-layer metric that reads it, or who else does


_TRAINER = "trainer API (spmd, optimizer, collective, fusion, overlap)"
_MODELS = "models (models/gpt2, remat)"
_SDAR = "models (models/sdar, remat)"
_LFM2 = "models (models/lfm2, remat)"
_GLM4 = "models (models/glm4_moe_lite, remat)"
_SMALLTHINKER = "models (models/smallthinker, remat)"
_EXPERTS = "expert layer (ops/moe)"
_KERNELS = "kernels (ops/flash_attention)"
_ENGINE = "engine (serving/engine, scheduler, cache)"
_COMPILER = "compiler (XLA, Mosaic, persistent cache)"
# A scope no metric has a file for still has its device time read: every
# traced run of the benchmark prints it (benchmark/readers/scopes.py).
_PRINTED = "the scope table's printed rows (a --trace 1 run)"
# A host span: the benchmark's loader keeps none yet (ROADMAP Design 6 a).
_XPROF = "xprof only (a host span, which no reader is handed yet)"

#: Every span, scope, kernel name and span counter the package emits. The
#: engine's phases are listed in the order one ``step_once`` runs them.
NAMES: Dict[str, Name] = {
    # host spans (jax.profiler.TraceAnnotation "hvd:<name>")
    "collective": Name(
        "span", _TRAINER, "the dispatch of one eager collective; args "
        "kind= and op_id= (the id the host timeline logs)", _XPROF),
    "engine.step": Name(
        "span", _ENGINE, "one step_once (an idle pass holds only its "
        "sweep and admit); args step=", _XPROF),
    "engine.sweep": Name(
        "span", _ENGINE, "finish and evict terminal lanes (before the "
        "dispatch and again after it, with the gauges)",
        "engine_host_ms.serve"),
    "engine.admit": Name(
        "span", _ENGINE, "pop ready requests, match prefixes, reserve "
        "blocks and slots", "engine_host_ms.serve"),
    "engine.build": Name(
        "span", _ENGINE, "the dispatch's host arrays, copy-on-write "
        "bookkeeping and their transfer", "engine_host_ms.serve"),
    "engine.dispatch": Name(
        "span", _ENGINE, "the jitted decode or prefill call, blocked "
        "until the device is done", _XPROF),
    "engine.readback": Name(
        "span", _ENGINE, "device to host: the greedy picks, and the "
        "logits when a lane samples", "engine_readback_ms.serve"),
    "engine.commit": Name(
        "span", _ENGINE, "the per-lane loop: verify drafts, commit "
        "tokens, push them to the stream", "engine_host_ms.serve"),
    # scopes under jit (jax.named_scope; operation metadata)
    "hvd/value_and_grad/sync": Name(
        "scope", _TRAINER, "hvd.value_and_grad's gradient sync",
        "grad_sync_mb.train (as the manifest's scope label); "
        "grad_sync_local_ms.train (its instructions that are no collective)"),
    "hvd/grad/sync": Name(
        "scope", _TRAINER, "hvd.grad's gradient sync (no overlap taps)",
        "grad_sync_mb.train (as the manifest's scope label); "
        "grad_sync_local_ms.train"),
    "hvd/tape/sync": Name(
        "scope", _TRAINER, "DistributedGradientTape.gradient's sync",
        "grad_sync_mb.train (as the manifest's scope label); "
        "grad_sync_local_ms.train"),
    "hvd/optimizer/sync": Name(
        "scope", _TRAINER, "DistributedOptimizer.update's gradient sync",
        "grad_sync_mb.train (as the manifest's scope label); "
        "grad_sync_local_ms.train"),
    "hvd/optimizer/update": Name(
        "scope", _TRAINER, "the inner optax update of "
        "DistributedOptimizer (where XLA fuses it into the weight-gradient "
        "products it reads as those: a fusion's scope is its root's)",
        _PRINTED),
    "hvd/fusion/pack": Name(
        "scope", _TRAINER, "ravel, slice and concatenate leaves into "
        "fusion buckets", "grad_sync_local_ms.train"),
    "hvd/fusion/unpack": Name(
        "scope", _TRAINER, "slice the reduced buckets back into leaves",
        "grad_sync_local_ms.train"),
    "gpt2/loss_head": Name(
        "scope", _MODELS, "models.gpt2.loss_fn: log-softmax over the "
        "vocabulary and the gather of the targets", "loss_head_ms.train"),
    "gpt2/attn": Name(
        "scope", _MODELS, "models.gpt2: a block's first half: ln1, the qkv "
        "projection, the attention call, the output projection and the "
        "residual add", _PRINTED + "; its kernels: flash_time_share.train"),
    "gpt2/mlp": Name(
        "scope", _MODELS, "models.gpt2: a block's second half: ln2, the two "
        "projections round the gelu (or the expert layer of an MoE "
        "configuration) and the residual add", _PRINTED),
    "gpt2/lm_head": Name(
        "scope", _MODELS, "models.gpt2: the final norm and the tied head's "
        "product in fp32 (the logits that gpt2/loss_head reads)", _PRINTED),
    "sdar/attn": Name(
        "scope", _SDAR, "models.sdar: the projections, QK-norm, RoPE, the "
        "attention call and the output projection of one layer",
        _PRINTED + "; its kernels: bd_flash_time_share.train"),
    "sdar/block": Name(
        "scope", _SDAR, "models.sdar: a block's second half round the expert "
        "layer: the norm before it and the residual add (moe/route and "
        "moe/experts nest inside)", _PRINTED + ": its own part"),
    "moe/route": Name(
        "scope", _EXPERTS, "ops.moe.routed_share: router logits and their "
        "scores in fp32 (softmax, or sigmoid with a selection bias), top-k, "
        "the sort of the local assignments by expert; flax puts the "
        "model's own module path before it (SDAR/h<i>/moe/moe/route)",
        _PRINTED),
    "moe/experts": Name(
        "scope", _EXPERTS, "ops.moe.routed_share: all of the share, forward "
        "and backward: the casts of the weights, the loop over windows with "
        "its carries, and a window's row gather, grouped products over the "
        "experts held (ragged-dot custom calls) and weighted sum back into "
        "positions",
        "moe_experts_outside_products_ms.train (all but the grouped "
        "products); moe_expert_time_share.train (the products, by "
        "instruction name)"),
    "sdar/loss_head": Name(
        "scope", _SDAR, "models.sdar.loss_fn: the head over the noisy "
        "half, log-softmax over the vocabulary slice, the masked 1/t "
        "weighting", _PRINTED),
    "lfm2/block": Name(
        "scope", _LFM2, "models.lfm2: all of a block: its own part is the "
        "two norms and, round the expert layer, the residual add (the "
        "operator's and the feed-forward's scopes nest inside)",
        _PRINTED + ": its own part, and all it holds"),
    "lfm2/shortconv": Name(
        "scope", _LFM2, "models.lfm2: one conv layer's operator: the "
        "projection to the gates and the value, the gated short "
        "convolution (ops.short_conv: XLA fusions without a name of their "
        "own) and the projection back", "shortconv_ms.train"),
    "lfm2/attn": Name(
        "scope", _LFM2, "models.lfm2: the projections, QK-norm, RoPE, the "
        "causal attention call and the output projection of an attention "
        "layer", _PRINTED + "; its kernels: lfm2_flash_time_share.train"),
    "lfm2/dense_mlp": Name(
        "scope", _LFM2, "models.lfm2: the dense SwiGLU of a leading block",
        _PRINTED),
    "lfm2/loss_head": Name(
        "scope", _LFM2, "models.lfm2.loss_fn: the tied head over the "
        "vocabulary slice, log-softmax, the gather of the next tokens",
        _PRINTED),
    "glm4/block": Name(
        "scope", _GLM4, "models.glm4_moe_lite: all of a block: its own part "
        "is the two norms and the residual adds (latent attention's, the "
        "feed-forward's and the expert layer's scopes nest inside)",
        _PRINTED + ": its own part, and all it holds"),
    "glm4/mla_down": Name(
        "scope", _GLM4, "models.glm4_moe_lite: latent attention's two "
        "down-projections (to the query bottleneck, and to the key/value "
        "latent with the shared RoPE key) and the norms inside them",
        _PRINTED),
    "glm4/mla_up": Name(
        "scope", _GLM4, "models.glm4_moe_lite: the up-projections to every "
        "head's query, key and value, RoPE, the broadcast of the one rotated "
        "key to all heads and the concatenations",
        "mla_expand_ms.train (what it writes: mla_kv_expanded_mb.train)"),
    "glm4/attn": Name(
        "scope", _GLM4, "models.glm4_moe_lite: the causal attention call at "
        "head 256 and the output projection",
        _PRINTED + "; its kernels: mla_flash_time_share.train"),
    "glm4/dense_mlp": Name(
        "scope", _GLM4, "models.glm4_moe_lite: the dense SwiGLU of a leading "
        "block", _PRINTED),
    "glm4/shared_expert": Name(
        "scope", _GLM4, "models.glm4_moe_lite: the shared expert of a routed "
        "block (ops.moe.SharedExpert), beside moe/route and moe/experts",
        _PRINTED),
    "glm4/mtp": Name(
        "scope", _GLM4, "models.glm4_moe_lite: everything of the "
        "multi-token-prediction module: its two norms, eh_proj, its block "
        "(whose own scopes nest inside this one) and its last norm",
        _PRINTED + ": its own part, and all it holds"),
    "glm4/loss_head": Name(
        "scope", _GLM4, "models.glm4_moe_lite.loss_terms: both passes of "
        "the untied head over the vocabulary slice, log-sum-exp minus the "
        "target's logit", "loss_head_ms.train_glm4"),
    "smallthinker/block": Name(
        "scope", _SMALLTHINKER, "models.smallthinker: all of a block: its "
        "own part is the two norms and the residual adds (the attention's "
        "scope nests inside; the route made from the block's input before "
        "the first norm is moe/route, the share moe/experts)",
        _PRINTED + ": its own part, and all it holds"),
    "smallthinker/attn_global": Name(
        "scope", _SMALLTHINKER, "models.smallthinker: a global layer's "
        "attention: the projections, no positional encoding, the causal "
        "attention call over every key and the output projection",
        _PRINTED + "; its kernels: swa_flash_time_share.train"),
    "smallthinker/attn_window": Name(
        "scope", _SMALLTHINKER, "models.smallthinker: a window layer's "
        "attention: the projections, RoPE, the causal attention call under "
        "the sliding window and the output projection",
        "window_attn_ms.train"),
    "smallthinker/loss_head": Name(
        "scope", _SMALLTHINKER, "models.smallthinker.loss_fn: the untied "
        "head over the vocabulary slice, log-sum-exp minus the target's "
        "logit", _PRINTED),
    "flash_attention": Name(
        "scope", _KERNELS, "round each flash kernel call, so that jax's "
        "jvp()/transpose() wrap this name and not the kernel's",
        _PRINTED + ": the three kernels together"),
    "flash/layout": Name(
        "scope", _KERNELS, "ops.flash_attention: the copies from [B,T,H,D] "
        "into the kernels' [B*H,T,D] (q, k, v) and back (the output), and "
        "their transposes in the backward: the price of that interface",
        "flash_layout_ms.train"),
    # kernel names (pallas_call(name=...): the custom call's instruction)
    "flash_fwd": Name(
        "kernel", _KERNELS, "flash attention forward (run again in the "
        "backward under remat=full; under dots its named output and "
        "log-sum-exp are kept)", "flash_fwd_ms.train"),
    "flash_dq": Name(
        "kernel", _KERNELS, "flash attention backward, dQ",
        "flash_dq_ms.train"),
    "flash_dkv": Name(
        "kernel", _KERNELS, "flash attention backward, dK and dV",
        "flash_dkv_ms.train"),
    # XLA's own name, of no call in this package: jax.lax.ragged_dot
    "ragged-dot": Name(
        "kernel", _EXPERTS, "the grouped products of ops.moe over the experts "
        "held (XLA's kernel for jax.lax.ragged_dot: custom calls "
        "%ragged-dot-none.N, which carry no op_name: the scope table gives "
        "them this row's layer)", "moe_expert_time_share.train (by "
        "instruction name); left out of "
        "moe_experts_outside_products_ms.train"),
    # counters and gauges of metrics.registry that this module writes
    "serve_step_phase_seconds_total": Name(
        "counter", _ENGINE, "host seconds per engine phase; labels "
        "engine, phase", "engine_host_ms.serve, engine_readback_ms.serve"),
    "serve_step_phase_total": Name(
        "counter", _ENGINE, "times each engine phase ran; labels engine, "
        "phase", "engine_host_ms.serve, engine_readback_ms.serve"),
    "grad_sync_bytes": Name(
        "gauge", _TRAINER, "sync manifest: bytes one device hands to "
        "all-reduce a step, as last traced; labels program, scope",
        "grad_sync_mb.train"),
    "grad_sync_buckets": Name(
        "gauge", _TRAINER, "sync manifest: fusion buckets reduced a step; "
        "labels program, scope", "registry only"),
    "grad_sync_passes": Name(
        "gauge", _TRAINER, "sync manifest: calls of allreduce_gradients "
        "that reached the wire; labels program, scope", "registry only"),
    "grad_sync_skipped": Name(
        "gauge", _TRAINER, "sync manifest: calls of allreduce_gradients "
        "that found every leaf averaged by an earlier pass of the trace "
        "and lowered nothing; labels program, scope", "registry only"),
    "moe_rows_bound": Name(
        "gauge", _EXPERTS, "routing manifest: the fallback's shape, the "
        "worst case that the loop over windows of moe_rows_tight rows may "
        "have to cover (positions x min(top_k, held): no assignment is "
        "ever dropped); label program",
        "registry only: what moe_rows_tight is a quarter of on the "
        "benchmark's cells"),
    "moe_rows_tight": Name(
        "gauge", _EXPERTS, "routing manifest: rows every d-wide operation "
        "of a step's share is shaped for (ops.moe.row_bounds: twice what a "
        "holder of held of experts_total experts expects, in tiles of 512, "
        "at most moe_rows_bound); label program",
        "moe_rows_filled_share.train"),
    "bd_tiles_visited": Name(
        "gauge", _KERNELS, "routing manifest: (Q tile, compute chunk) "
        "pairs of one head's forward under the block-diffusion mask that "
        "hold a visible pair, the ones the kernels' inner loop visits; "
        "tiles of the grid where the kernels do not loop "
        "(ops/flash_attention.bd_tiles); label program",
        "bd_tiles_visited_share.train"),
    "bd_tiles_total": Name(
        "gauge", _KERNELS, "routing manifest: (Q tile, compute chunk) "
        "pairs of one head's forward under the block-diffusion mask, or "
        "tiles of its grid; label program", "bd_tiles_visited_share.train"),
    "causal_tiles_visited": Name(
        "gauge", _KERNELS, "routing manifest: (Q tile, compute chunk) "
        "pairs of one head's causal forward that hold a visible pair, the "
        "ones the kernels' inner loop visits; label program",
        "causal_tiles_visited_share.train"),
    "causal_tiles_total": Name(
        "gauge", _KERNELS, "routing manifest: (Q tile, compute chunk) "
        "pairs of one head's causal forward; label program",
        "causal_tiles_visited_share.train"),
    "window_tiles_visited": Name(
        "gauge", _KERNELS, "routing manifest: (Q tile, compute chunk) "
        "pairs of one head's forward under a sliding window that hold a "
        "visible pair, the ones the kernels visit: between the band's lower "
        "edge and the diagonal (ops/flash_attention.window_tiles); label "
        "program", "window_tiles_visited_share.train"),
    "window_tiles_total": Name(
        "gauge", _KERNELS, "routing manifest: (Q tile, compute chunk) "
        "pairs of one head's forward under a sliding window; label program",
        "window_tiles_visited_share.train"),
    "flash_bwd_kernels": Name(
        "gauge", _KERNELS, "routing manifest: kernels the backward of the "
        "program's flash attention calls is (as last traced): 1 where the "
        "dK/dV kernel loops over the chunks of a resident K tile and sums "
        "dQ beside them (no flash_dq call), 2 where flash_dq and flash_dkv "
        "each visit the pairs; label program", "flash_bwd_kernels.train"),
    "flash_bwd_vmem_bytes": Name(
        "gauge", _KERNELS, "routing manifest: bytes of VMEM that backward's "
        "dK/dV call asked the compiler for (vmem_limit_bytes: "
        "ops/flash_attention._vmem_need); 0 where the compiler's default "
        "covers it and the call asks for nothing; label program",
        "registry only: what a resident K tile costs at this shape"),
    "mla_kv_expanded_bytes": Name(
        "gauge", _GLM4, "routing manifest: bytes a step writes as per-head "
        "keys and values before the attention kernels (positions x "
        "attention layers x heads x (key + value head size) x itemsize; "
        "the one rotated key counted once a head it is copied to); label "
        "program", "mla_kv_expanded_mb.train"),
    "mla_latent_bytes": Name(
        "gauge", _GLM4, "routing manifest: bytes of what those keys and "
        "values are expanded from (positions x attention layers x (latent "
        "+ rotated key) x itemsize); label program",
        "registry only: what mla_kv_expanded_bytes is a multiple of (17.8 "
        "on the benchmark's cell)"),
    "mtp_modules": Name(
        "gauge", _GLM4, "routing manifest: multi-token-prediction modules "
        "in the program's loss (1, or 0 without); label program",
        "registry only: that the module is in what is timed"),
    "flash_residuals_saved": Name(
        "gauge", _MODELS, "remat count: times a block's dots policy "
        "answered save for the flash forward's named output or row "
        "log-sum-exp while the program was last traced (a count of "
        "answers, a multiple of the layers: two a layer with jax 0.9; 0 "
        "under full, without remat, with dense attention); label program",
        "registry only: flash_fwd_ms.train reads the effect on the device "
        "(one forward call a layer, not two)"),
    "moe_local_assignments": Name(
        "gauge", _EXPERTS, "routing of one batch (routing_load): "
        "(position, expert) choices that fell on the experts held, a "
        "layer; label program", "moe_local_assignments.train"),
    "moe_rows_overflow_layers": Name(
        "gauge", _EXPERTS, "routing of one batch (routing_load): routed "
        "layers whose rows exceed the moe_rows_tight the program "
        "published, the layers that go over a second window of rows; label "
        "program", "registry only: 0 on the benchmark's cells; how often "
        "more than the tight shape is paid for"),
    "moe_load_max_over_mean": Name(
        "gauge", _EXPERTS, "routing of one batch: the busiest held "
        "expert's rows over the mean, over layers; label program",
        "registry only: the imbalance an operator looks at before blaming "
        "the grouped products, whose time follows the rows they are given"),
    "moe_bias_moved_share": Name(
        "gauge", _EXPERTS, "routing of one batch (routing_bias_moved): the "
        "share of a layer's choices that the top-k of the unbiased scores "
        "would not have made, mean over layers: 0 where the selection "
        "bias is absent or moves nothing; label program",
        "moe_bias_moved_share.train"),
    "jax_compile_seconds_total": Name(
        "counter", _COMPILER, "set-up ledger: seconds jax reports per "
        "phase (trace, lower, backend, cache_load) and function; an outer "
        "function's seconds include the functions traced inside it",
        "step_trace_lower_s.train"),
    "jax_compile_total": Name(
        "counter", _COMPILER, "set-up ledger: events behind the seconds",
        "step_trace_lower_s.train"),
    "import_seconds": Name(
        "gauge", _TRAINER, "seconds `import horovod_tpu` took (jax "
        "imported before it or not)", "program_import_init_s"),
}

SPAN_PREFIX = "hvd:"


def span(name: str, **args):
    """Host span ``hvd:<name>`` on the profiler's clock. ``args`` carry
    the identifier that the spans of one request or step share
    (``step=``, ``request=``). Free when no profiler session is on."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)


@contextmanager
def scope(name: str):
    """``jax.named_scope(name)`` for code under ``jit``, as a context or
    as a decorator; the innermost open one is :func:`current_scope`. Runs
    while tracing only."""
    stack = _TLS.__dict__.setdefault("scopes", [])
    stack.append(name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        stack.pop()


def current_scope() -> str:
    """The innermost open :func:`scope` of this thread, or ``"none"``."""
    stack = getattr(_TLS, "scopes", None)
    return stack[-1] if stack else "none"


@contextmanager
def timed(name: str, family: str, step: int, /, **labels):
    """:func:`span` ``name`` of step ``step`` that also adds its host
    seconds to ``<family>_seconds_total`` and one to ``<family>_total``,
    labelled ``labels`` and ``phase=`` the last dotted part of ``name``."""
    phase_name = name.rsplit(".", 1)[-1]
    t0 = time.perf_counter()
    try:
        with span(name, step=step):
            yield
    finally:
        dt = time.perf_counter() - t0
        _metrics.counter(family + "_seconds_total", phase=phase_name,
                         **labels).inc(dt)
        _metrics.counter(family + "_total", phase=phase_name,
                         **labels).inc()


# ---------------------------------------------------------------------------
# the sync manifest
# ---------------------------------------------------------------------------

_PROGRAMS: set = set()          # names the package's own programs carry
_PUBLISHED: Dict[str, set] = {}  # program -> scopes it has gauges for


def note_program(name: str) -> None:
    """A program the package itself builds: the set-up ledger keeps a
    series for it instead of counting it under ``other``."""
    _PROGRAMS.add(name)


_COUNTS = ("bytes", "buckets", "passes", "skipped")


@contextmanager
def program(name: str):
    """The function ``name`` is being traced on this thread: gradient
    syncs inside belong to its manifest, published when the trace ends
    as the gauges ``grad_sync_{bytes,buckets,passes,skipped}{program,
    scope}``. The last trace's values: a program lowered twice is not
    counted twice, and a second program does not add to the first's.
    What :func:`note_routing` was told inside is published the same way,
    as gauges ``{program}``, and so is what :func:`note_residual_saved`
    counted (every program says it, 0 included). The leaves marked by
    :func:`mark_synced` are kept until the trace ends and no longer, so
    that no tracer outlives its trace. A trace that :func:`scope_table`
    itself causes publishes nothing."""
    prev = (getattr(_TLS, "manifest", None), getattr(_TLS, "synced", None),
            getattr(_TLS, "routing", None), getattr(_TLS, "saved", None))
    manifest: Dict[str, list] = {}
    routing: Dict[str, float] = {}
    saved = [0]
    _TLS.manifest, _TLS.synced, _TLS.routing, _TLS.saved = (
        manifest, {}, routing, saved)
    try:
        yield
    finally:
        _TLS.manifest, _TLS.synced, _TLS.routing, _TLS.saved = prev
        if getattr(_TLS, "asking", False):
            return      # scope_table's own lowering: the gauges stand
        for key, v in routing.items():
            _metrics.gauge(key, program=name).set(v)
        _metrics.gauge("flash_residuals_saved", program=name).set(saved[0])
        with _LOCK:
            stale = _PUBLISHED.get(name, set()) - set(manifest)
            _PUBLISHED[name] = set(manifest)
        for sc in stale:
            manifest[sc] = [0] * len(_COUNTS)
        for sc, counts in manifest.items():
            for what, v in zip(_COUNTS, counts):
                _metrics.gauge("grad_sync_" + what, program=name,
                               scope=sc).set(v)


@contextmanager
def sync_pass(peers: int, skipped: bool = False):
    """One gradient sync over ``peers`` devices is being lowered, called
    from :func:`current_scope`. With one device nothing reaches the
    wire and the pass counts nothing (the scope still gets its zeros).
    ``skipped``: the pass found its gradients synchronised already
    (:func:`synced_as`) and lowers nothing; it counts as that."""
    manifest = getattr(_TLS, "manifest", None)
    if manifest is None:
        yield
        return
    entry = manifest.setdefault(current_scope(), [0] * len(_COUNTS))
    prev = getattr(_TLS, "sync_entry", None)
    if skipped:
        entry[3] += 1
    elif peers > 1:
        entry[2] += 1
        _TLS.sync_entry = entry
    try:
        yield
    finally:
        _TLS.sync_entry = prev


def note_bucket(nbytes: int) -> None:
    """A bucket of ``nbytes`` is handed to its collective (the payload as
    it goes: after compression and wire cast, before any leg split)."""
    entry = getattr(_TLS, "sync_entry", None)
    if entry is not None:
        entry[0] += int(nbytes)
        entry[1] += 1


def mark_synced(tree: Any, what: Any) -> None:
    """Every leaf of ``tree`` is what a finished gradient sync of this
    trace returned; ``what`` says what the sync gave (its op and its
    resolved process set). Kept by the leaf object itself (a strong
    reference keyed by ``id``, so that the ``id`` cannot be re-used while
    it is a key) until :func:`program` exits. Outside a program nothing
    is kept."""
    synced = getattr(_TLS, "synced", None)
    if synced is not None:
        for leaf in jax.tree_util.tree_leaves(tree):
            synced[id(leaf)] = (leaf, what)


def synced_as(tree: Any) -> Any:
    """What :func:`mark_synced` said of the leaves of ``tree``, if it said
    the same of every one of them (the very objects, compared with
    ``is``: anything computed from a marked leaf is a new object and not
    marked); else None. All or nothing: one new leaf, and the tree is not
    synchronised."""
    synced = getattr(_TLS, "synced", None)
    if not synced:
        return None
    said = []
    for leaf in jax.tree_util.tree_leaves(tree):
        kept = synced.get(id(leaf))
        if kept is None or kept[0] is not leaf:
            return None
        said.append(kept[1])
    if not said or any(what != said[0] for what in said):
        return None
    return said[0]


# ---------------------------------------------------------------------------
# the routing manifest
# ---------------------------------------------------------------------------

_ROUTING = ("moe_rows_bound", "moe_rows_tight", "bd_tiles_visited",
            "bd_tiles_total", "causal_tiles_visited", "causal_tiles_total",
            "mla_kv_expanded_bytes", "mla_latent_bytes", "mtp_modules",
            "flash_bwd_kernels", "flash_bwd_vmem_bytes",
            "window_tiles_visited", "window_tiles_total")


def note_routing(**shapes) -> None:
    """A routed expert layer, a block-diffusion, causal or sliding-window
    attention call, or a model with latent attention is being traced: what
    it is shaped for, from static values (:data:`_ROUTING` names them).
    Published as
    gauges ``{program}`` when :func:`program` exits; every layer of a
    program says the same, and the last one stands. Outside a program
    nothing is kept."""
    unknown = set(shapes) - set(_ROUTING)
    if unknown:
        raise ValueError(f"not in the routing manifest: {sorted(unknown)}")
    routing = getattr(_TLS, "routing", None)
    if routing is not None:
        routing.update(shapes)


def routing_load(program_name: str, group_sizes) -> None:
    """How the routing of one batch loaded the experts held:
    ``group_sizes`` ``(layers, held)`` are the rows each held expert was
    given, an auxiliary output of a forward pass (a check step, never a
    timed one). Sets ``moe_local_assignments`` (rows a layer, mean over
    layers), ``moe_load_max_over_mean`` and, where the program published
    the ``moe_rows_tight`` its share is shaped for,
    ``moe_rows_overflow_layers``: the layers whose rows exceed it."""
    import numpy as np
    sizes = np.asarray(group_sizes, dtype=np.float64)
    sizes = sizes.reshape(-1, sizes.shape[-1])
    rows = sizes.sum(axis=1)
    _metrics.gauge("moe_local_assignments", program=program_name).set(
        float(rows.mean()))
    tight = [s["value"] for s in _metrics.snapshot()["gauges"].get(
        "moe_rows_tight", ()) if s["labels"].get("program") == program_name]
    if tight:
        _metrics.gauge("moe_rows_overflow_layers", program=program_name).set(
            int((rows > tight[0]).sum()))
    mean = sizes.mean()
    _metrics.gauge("moe_load_max_over_mean", program=program_name).set(
        float(sizes.max() / mean) if mean else 0.0)


def routing_bias_moved(program_name: str, moved, choices: int) -> None:
    """What a selection bias did to the routing of one batch: ``moved``
    (layers,) are the choices of each routed layer that the top-k of the
    unbiased scores would not have made, of ``choices`` a layer; like
    :func:`routing_load` from a forward pass's auxiliary output, never from
    a timed step. Sets ``moe_bias_moved_share`` (a share of 1, mean over
    layers)."""
    import numpy as np
    _metrics.gauge("moe_bias_moved_share", program=program_name).set(
        float(np.mean(np.asarray(moved, dtype=np.float64)) / choices))


# ---------------------------------------------------------------------------
# the remat count
# ---------------------------------------------------------------------------

def note_residual_saved() -> None:
    """The remat policy of a block answered "save" for a residual that the
    flash forward named (``models/remat.py``; ``ops/flash_attention.
    RESIDUAL_NAMES``). How often jax asks a policy about one equation
    while it splits a block into what is kept and what is run again is
    jax's own business (0.9 asks once: two answers a layer), so the gauge
    ``flash_residuals_saved{program}`` that :func:`program` publishes is
    a count of answers and not of arrays: positive and proportional to
    the layers under ``dots`` with flash attention, 0 under ``full``,
    without remat and with dense attention. Outside a program nothing is
    kept."""
    saved = getattr(_TLS, "saved", None)
    if saved is not None:
        saved[0] += 1


# ---------------------------------------------------------------------------
# the scope table
# ---------------------------------------------------------------------------

class ScopeRow(NamedTuple):
    """One instruction of a compiled program, as :func:`scope_table` reads
    it."""
    scopes: Tuple[str, ...]     # NAMES rows of kind scope, outermost first
    layer: Optional[str]        # the innermost one's layer; else the
    #                             kernel row's; None: no name of ours
    direction: str              # fwd | remat | bwd
    container: bool             # its device event covers its body's events
    kernel: Optional[str]       # the NAMES kernel row its name holds
    op_name: str                # the metadata read, "" where it has none


#: Opcodes whose device event spans the events of the computations they
#: call: summing them beside their bodies would count the bodies twice.
CONTAINERS = ("while", "conditional", "call")

_LOWERINGS: Dict[str, tuple] = {}   # program -> (weak jit, its arguments)
_TABLES: Dict[str, tuple] = {}      # program -> (the lowering read, table)


def note_lowering(name: str, jitted: "weakref.ref", args: Any) -> None:
    """What lowers the program ``name`` again: a weak reference to the
    jitted function (the table never keeps a program alive) and its
    arguments as ``jax.ShapeDtypeStruct``s with global shapes and
    shardings. Called by ``hvd.spmd`` while jit traces the function, never
    per step; the last trace stands, as with the manifests."""
    if getattr(_TLS, "asking", False):
        _TLS.retraced = True    # scope_table's lowering missed jit's cache
        return
    with _LOCK:
        _LOWERINGS[name] = (jitted, args)


def scope_table(program: str) -> Optional[Dict[str, ScopeRow]]:
    """``{instruction name: ScopeRow}`` over the compiled text of the
    ``hvd.spmd`` program ``program`` as it was last traced, or None (no
    such program, never traced, static arguments, or the function is gone).
    The instruction names are those a device trace's events carry
    (``%fusion.349 = ...`` is ``fusion.349``), so a reader sums device time
    by scope by looking each event up here. Rows are the instructions of
    the entry computation and of every computation a ``while``,
    ``conditional`` or ``call`` runs, each once; what a fusion holds inside
    is not listed, as it never runs as an event of its own.

    The rules, which the compiled text fixes and nothing here chooses:

    * ``scopes`` are the :data:`NAMES` rows of kind ``scope`` found as whole
      path segments of the instruction's ``op_name``, outermost first
      (``glm4/mtp`` round ``glm4/attn`` round ``flash_attention`` keeps all
      three; the module path flax puts before a scope is not a scope).
    * **A fusion's scope is its root's**: XLA gives a fusion instruction the
      metadata of its root, so a layer's elementwise tail fused into the
      next layer's product counts for the product's scope.
    * **A kernel is never unscoped**: an instruction whose name holds a
      :data:`NAMES` row of kind ``kernel`` (``flash_fwd.7``,
      ``ragged-dot-none.12``, whose custom calls carry no metadata) has
      that row as ``kernel`` and, where it lies in no scope, its layer.
    * ``direction``: ``remat`` where the ``op_name`` holds
      ``rematted_computation`` (the forward that ``jax.checkpoint`` runs
      again inside the backward; it lies inside ``transpose(`` too), else
      ``bwd`` where it holds ``transpose(``, else ``fwd`` (the optimizer
      update and the gradient sync read ``fwd``: they are not
      differentiated). A ``custom_vjp``'s backward that runs its forward
      again by itself (the expert layer's) is ``bwd``.
    * ``container``: a ``while``, ``conditional`` or ``call``, whose device
      event covers its body's; the body's instructions are rows of their
      own.

    What asking costs: nothing until it is asked, nothing per step. The
    first call lowers the program again from the noted shapes and compiles
    it. In a process that has run the program both are answered by jax's
    own in-memory caches (the function's Python does not run, nothing is
    compiled: a few seconds for the text of a large step), and the table is
    of the very executable the process runs, so every event's name is in
    it. That executable's metadata are its writer's: where the persistent
    cache answered the set-up's compile with an entry another checkout
    wrote (jax keys an entry without its metadata), the scopes are that
    checkout's. A process that never compiled the program compiles it here,
    with the metadata in the persistent cache's key, so that no other
    checkout's entry answers. A step called with some placed arrays and
    some not is traced again, in silence. The result is kept until the
    program is traced anew; the set-up ledger books none of this and no
    manifest gauge moves."""
    with _LOCK:
        noted = _LOWERINGS.get(program)
        kept = _TABLES.get(program)
    if noted is None:
        return None
    if kept is not None and kept[0] is noted:
        return kept[1]
    jitted = noted[0]()
    if jitted is None:
        return None
    _TLS.asking = True
    try:
        # Committed arrays give jit their shardings, and so do these
        # shapes; arrays that were never placed give it none. Whichever
        # jit's trace cache knows is the program that ran.
        for args in (noted[1], jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               weak_type=x.weak_type),
                noted[1])):
            _TLS.retraced = False
            lowered = jitted.lower(*args)
            if not _TLS.retraced:
                break
        # Where the process has compiled this program, jax's in-memory
        # cache answers with that executable. Where it has not, the
        # persistent cache would: it keys a program without its metadata,
        # so an entry another checkout wrote (the parent commit's, whose
        # program differs in scopes alone) would answer with that
        # checkout's op_names. For this one compile the metadata is part
        # of the key.
        flag = "jax_compilation_cache_include_metadata_in_key"
        keyed = getattr(jax.config, flag)
        jax.config.update(flag, True)
        try:
            text = lowered.compile().as_text()
        finally:
            jax.config.update(flag, keyed)
    finally:
        _TLS.asking = False
    table = _read_scopes(text)
    with _LOCK:
        _TABLES[program] = (noted, table)
    return table


_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:body|condition|to_apply|calls|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after `` = ``: past its shape (a
    tuple's may hold spaces and comments), up to the operands."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return rest.lstrip().partition("(")[0].strip()


def _read_scopes(text: str) -> Dict[str, ScopeRow]:
    """:func:`scope_table`'s pass over a compiled program's text."""
    scope_names = sorted((n for n, row in NAMES.items()
                          if row.kind == "scope"), key=len, reverse=True)
    kernels = [n for n, row in NAMES.items() if row.kind == "kernel"]
    segment = re.compile(r"(?<![\w.\-])(?:" + "|".join(
        re.escape(n) for n in scope_names) + r")(?![\w.\-])")
    read: Dict[str, tuple] = {}     # op_name -> (scopes, direction)

    def of(op_name):
        hit = read.get(op_name)
        if hit is None:
            scopes = tuple(dict.fromkeys(segment.findall(op_name)))
            direction = ("remat" if "rematted_computation" in op_name
                         else "bwd" if "transpose(" in op_name else "fwd")
            hit = read[op_name] = (scopes, direction)
        return hit

    # every computation's instruction lines; only those that run as events
    # (the entry's, and on from there what a container calls) are read
    computations: Dict[str, list] = {}
    entry = current = None
    for line in text.splitlines():
        if current is None:
            head = _COMPUTATION.match(line)
            if head:
                current = computations[head.group(2)] = []
                if head.group(1):
                    entry = head.group(2)
        elif line.startswith("}"):
            current = None
        else:
            m = _INSTRUCTION.match(line)
            if m is not None:
                current.append(m.groups())

    table: Dict[str, ScopeRow] = {}
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in computations:
            continue
        seen.add(comp)
        for name, rest in computations[comp]:
            container = _opcode(rest) in CONTAINERS
            if container:
                todo += _CALLED.findall(rest)
                for group in _BRANCHES.findall(rest):
                    todo += [c.strip().lstrip("%") for c in group.split(",")]
            op_name = _OP_NAME.search(rest)
            op_name = op_name.group(1) if op_name else ""
            scopes, direction = of(op_name)
            kernel = next((k for k in kernels if k in name), None)
            layer = (NAMES[scopes[-1]].layer if scopes
                     else NAMES[kernel].layer if kernel else None)
            table[name] = ScopeRow(scopes, layer, direction, container,
                                   kernel, op_name)
    return table


# ---------------------------------------------------------------------------
# the set-up ledger
# ---------------------------------------------------------------------------

_JAX_PHASE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


def _ledger(phase_name: str, fun: str, secs: float) -> None:
    _metrics.counter("jax_compile_seconds_total", phase=phase_name,
                     fun=fun).inc(secs)
    _metrics.counter("jax_compile_total", phase=phase_name, fun=fun).inc()


def _on_jax_duration(event: str, secs: float, **kw) -> None:
    """jax's own clock on the four phases of making a program. ``fun`` is
    the function's name for the package's programs (:func:`note_program`)
    and ``other`` for the rest, so the series stay bounded. jax reports a
    cache load without a name, inside the backend event that follows it
    on the same thread: the load is booked under that event's function
    and taken out of its ``backend`` seconds, so the phases add up. What
    :func:`scope_table` lowers and loads to read a program's text is not
    set-up and is not booked."""
    phase_name = _JAX_PHASE.get(event)
    if phase_name is None or getattr(_TLS, "asking", False):
        return          # not a phase, or scope_table's own lowering
    if phase_name == "cache_load":
        _TLS.cache_load = getattr(_TLS, "cache_load", 0.0) + secs
        return
    fun = str(kw.get("fun_name", ""))
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]                 # lower and backend say jit(<name>)
    if fun not in _PROGRAMS:
        fun = "other"
    if phase_name == "backend":
        loaded = getattr(_TLS, "cache_load", 0.0)
        if loaded:
            _TLS.cache_load = 0.0
            _ledger("cache_load", fun, loaded)
            secs = max(0.0, secs - loaded)
    _ledger(phase_name, fun, secs)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
