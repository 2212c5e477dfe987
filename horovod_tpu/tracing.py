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
  block-diffusion and causal attention calls and the latent attention of a
  program are shaped for, noted while the program is traced
  (:func:`note_routing`), and how the routing of one batch loaded the
  experts held (:func:`routing_load`).
* **The remat count** — how often a block's remat policy kept a residual
  that the flash forward named, while the program is traced
  (:func:`note_residual_saved`).
* **The set-up ledger** — one ``jax.monitoring`` listener, registered when
  this module is imported, that adds jax's own trace / lower / backend /
  cache-load seconds to ``jax_compile_seconds_total{phase,fun}``.

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

import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, NamedTuple, Optional

import jax

from horovod_tpu import metrics as _metrics

__all__ = ["Span", "mint_span", "current_span", "active_span",
           "reset_spans", "phase",
           "NAMES", "Name", "span", "scope", "timed", "current_scope",
           "program", "note_program", "sync_pass", "note_bucket",
           "mark_synced", "synced_as", "note_routing", "routing_load",
           "routing_bias_moved", "note_residual_saved"]

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
    feeds: str      # the per-layer metric that reads it, or "xprof only"


_TRAINER = "trainer API (spmd, optimizer, collective, fusion, overlap)"
_MODELS = "models (models/gpt2, remat)"
_SDAR = "models (models/sdar, remat)"
_LFM2 = "models (models/lfm2, remat)"
_GLM4 = "models (models/glm4_moe_lite, remat)"
_EXPERTS = "expert layer (ops/moe)"
_KERNELS = "kernels (ops/flash_attention)"
_ENGINE = "engine (serving/engine, scheduler, cache)"
_COMPILER = "compiler (XLA, Mosaic, persistent cache)"

#: Every span, scope, kernel name and span counter the package emits. The
#: engine's phases are listed in the order one ``step_once`` runs them.
NAMES: Dict[str, Name] = {
    # host spans (jax.profiler.TraceAnnotation "hvd:<name>")
    "collective": Name(
        "span", _TRAINER, "the dispatch of one eager collective; args "
        "kind= and op_id= (the id the host timeline logs)", "xprof only"),
    "engine.step": Name(
        "span", _ENGINE, "one step_once (an idle pass holds only its "
        "sweep and admit); args step=", "xprof only"),
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
        "until the device is done", "xprof only"),
    "engine.readback": Name(
        "span", _ENGINE, "device to host: the greedy picks, and the "
        "logits when a lane samples", "engine_readback_ms.serve"),
    "engine.commit": Name(
        "span", _ENGINE, "the per-lane loop: verify drafts, commit "
        "tokens, push them to the stream", "engine_host_ms.serve"),
    # scopes under jit (jax.named_scope; operation metadata)
    "hvd/value_and_grad/sync": Name(
        "scope", _TRAINER, "hvd.value_and_grad's gradient sync",
        "grad_sync_mb.train (as the manifest's scope label)"),
    "hvd/grad/sync": Name(
        "scope", _TRAINER, "hvd.grad's gradient sync (no overlap taps)",
        "grad_sync_mb.train (as the manifest's scope label)"),
    "hvd/tape/sync": Name(
        "scope", _TRAINER, "DistributedGradientTape.gradient's sync",
        "grad_sync_mb.train (as the manifest's scope label)"),
    "hvd/optimizer/sync": Name(
        "scope", _TRAINER, "DistributedOptimizer.update's gradient sync",
        "grad_sync_mb.train (as the manifest's scope label)"),
    "hvd/optimizer/update": Name(
        "scope", _TRAINER, "the inner optax update of "
        "DistributedOptimizer", "xprof only"),
    "hvd/fusion/pack": Name(
        "scope", _TRAINER, "ravel, slice and concatenate leaves into "
        "fusion buckets", "xprof only"),
    "hvd/fusion/unpack": Name(
        "scope", _TRAINER, "slice the reduced buckets back into leaves",
        "xprof only"),
    "gpt2/loss_head": Name(
        "scope", _MODELS, "models.gpt2.loss_fn: log-softmax over the "
        "vocabulary and the gather of the targets", "xprof only"),
    "sdar/attn": Name(
        "scope", _SDAR, "models.sdar: the projections, QK-norm, RoPE, the "
        "attention call and the output projection of one layer",
        "xprof only"),
    "moe/route": Name(
        "scope", _EXPERTS, "ops.moe.routed_share: router logits and their "
        "scores in fp32 (softmax, or sigmoid with a selection bias), top-k, "
        "the sort of the local assignments by expert; flax puts the "
        "model's own module path before it (SDAR/h<i>/moe/moe/route)",
        "xprof only"),
    "moe/experts": Name(
        "scope", _EXPERTS, "ops.moe.routed_share: gather, the grouped "
        "products over the experts held (ragged-dot custom calls), the "
        "weighted sum back into positions",
        "moe_expert_time_share.train (the grouped products, by "
        "instruction name)"),
    "sdar/loss_head": Name(
        "scope", _SDAR, "models.sdar.loss_fn: the head over the noisy "
        "half, log-softmax over the vocabulary slice, the masked 1/t "
        "weighting", "xprof only"),
    "lfm2/shortconv": Name(
        "scope", _LFM2, "models.lfm2: one conv layer's operator: the "
        "projection to the gates and the value, the gated short "
        "convolution (ops.short_conv: XLA fusions without a name of their "
        "own) and the projection back", "xprof only (PERF.md section 5 "
        "gives its device time by hand, from a kept trace)"),
    "lfm2/attn": Name(
        "scope", _LFM2, "models.lfm2: the projections, QK-norm, RoPE, the "
        "causal attention call and the output projection of an attention "
        "layer", "xprof only (its kernels: lfm2_flash_time_share.train)"),
    "lfm2/dense_mlp": Name(
        "scope", _LFM2, "models.lfm2: the dense SwiGLU of a leading block",
        "xprof only"),
    "lfm2/loss_head": Name(
        "scope", _LFM2, "models.lfm2.loss_fn: the tied head over the "
        "vocabulary slice, log-softmax, the gather of the next tokens",
        "xprof only"),
    "glm4/mla_down": Name(
        "scope", _GLM4, "models.glm4_moe_lite: latent attention's two "
        "down-projections (to the query bottleneck, and to the key/value "
        "latent with the shared RoPE key) and the norms inside them",
        "xprof only (PERF.md section 5 gives its device time by hand)"),
    "glm4/mla_up": Name(
        "scope", _GLM4, "models.glm4_moe_lite: the up-projections to every "
        "head's query, key and value, RoPE, the broadcast of the one rotated "
        "key to all heads and the concatenations",
        "xprof only (what it writes: mla_kv_expanded_mb.train)"),
    "glm4/attn": Name(
        "scope", _GLM4, "models.glm4_moe_lite: the causal attention call at "
        "head 256 and the output projection",
        "xprof only (its kernels: mla_flash_time_share.train)"),
    "glm4/dense_mlp": Name(
        "scope", _GLM4, "models.glm4_moe_lite: the dense SwiGLU of a leading "
        "block", "xprof only"),
    "glm4/shared_expert": Name(
        "scope", _GLM4, "models.glm4_moe_lite: the shared expert of a routed "
        "block (ops.moe.SharedExpert), beside moe/route and moe/experts",
        "xprof only (PERF.md section 5 gives its device time by hand)"),
    "glm4/mtp": Name(
        "scope", _GLM4, "models.glm4_moe_lite: everything of the "
        "multi-token-prediction module: its two norms, eh_proj, its block "
        "(whose own scopes nest inside this one) and its last norm",
        "xprof only (PERF.md section 5 gives its device time by hand)"),
    "glm4/loss_head": Name(
        "scope", _GLM4, "models.glm4_moe_lite.loss_terms: both passes of "
        "the untied head over the vocabulary slice, log-sum-exp minus the "
        "target's logit", "xprof only"),
    "flash_attention": Name(
        "scope", _KERNELS, "round each flash kernel call, so that jax's "
        "jvp()/transpose() wrap this name and not the kernel's",
        "xprof only"),
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
        "labels program, scope", "xprof only"),
    "grad_sync_passes": Name(
        "gauge", _TRAINER, "sync manifest: calls of allreduce_gradients "
        "that reached the wire; labels program, scope", "xprof only"),
    "grad_sync_skipped": Name(
        "gauge", _TRAINER, "sync manifest: calls of allreduce_gradients "
        "that found every leaf averaged by an earlier pass of the trace "
        "and lowered nothing; labels program, scope", "xprof only"),
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
        "gauge", _KERNELS, "routing manifest: tiles of one head's forward "
        "grid that hold a visible pair; label program",
        "bd_tiles_visited_share.train"),
    "bd_tiles_total": Name(
        "gauge", _KERNELS, "routing manifest: tiles of one head's forward "
        "grid; label program", "bd_tiles_visited_share.train"),
    "causal_tiles_visited": Name(
        "gauge", _KERNELS, "routing manifest: (Q tile, compute chunk) "
        "pairs of one head's causal forward that hold a visible pair, the "
        "ones the kernels' inner loop visits; label program",
        "causal_tiles_visited_share.train"),
    "causal_tiles_total": Name(
        "gauge", _KERNELS, "routing manifest: (Q tile, compute chunk) "
        "pairs of one head's causal forward; label program",
        "causal_tiles_visited_share.train"),
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
    that no tracer outlives its trace."""
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
            "mla_kv_expanded_bytes", "mla_latent_bytes", "mtp_modules")


def note_routing(**shapes) -> None:
    """A routed expert layer, a block-diffusion or a causal attention call,
    or a model with latent attention is being traced: what it is shaped
    for, from static values (:data:`_ROUTING` names them). Published as
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
    and taken out of its ``backend`` seconds, so the phases add up."""
    phase_name = _JAX_PHASE.get(event)
    if phase_name is None:
        return
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
