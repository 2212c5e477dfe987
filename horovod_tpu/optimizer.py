"""Distributed optimizer and gradient synchronization.

Rebuild of upstream ``horovod/torch/optimizer.py`` (hook-based
DistributedOptimizer) and ``horovod/tensorflow/__init__.py``
(DistributedGradientTape / DistributedOptimizer). The reference intercepts
gradients as they become ready and enqueues allreduces through the fusion
pipeline; the optimizer step waits on the handles.

TPU-native shape: gradients live in one pytree inside a jitted SPMD step, so
"interception" is a gradient transformation: :func:`DistributedOptimizer`
wraps any optax ``GradientTransformation`` so its ``update`` first
fuse+compress+allreduces the gradient pytree over the communicator axis, then
delegates. XLA overlaps the fused psums with the optimizer math — the manual
ready-ordering/stream machinery of the reference is the compiler's job here.

When the step is *not* running under ``shard_map`` (i.e. the user relies on
``jit`` auto-sharding where XLA already inserts gradient psums), the wrapper
is an identity on gradients, so the same training script works in both modes.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from horovod_tpu import collective as C
from horovod_tpu import core
from horovod_tpu import metrics as _metrics
from horovod_tpu import tracing as _tracing
from horovod_tpu.compression import Compression
from horovod_tpu.process_set import ProcessSet

__all__ = [
    "DistributedOptimizer", "DistributedGradientTape", "grad",
    "value_and_grad", "allreduce_gradients", "AutotunedStep",
    "ErrorFeedbackState", "reset_error_feedback",
    "broadcast_parameters", "broadcast_optimizer_state", "broadcast_variables",
]


class ErrorFeedbackState(NamedTuple):
    """Optimizer state of a :func:`DistributedOptimizer` with
    ``error_feedback=True``: the wrapped transform's state plus the
    per-parameter quantization residual carried across steps."""
    inner: Any
    residual: Any


def _effective_quant_wire(algorithm: Optional[str],
                          wire: Optional[str] = None) -> Optional[str]:
    """The quantized wire format a gradient allreduce will use, or None.

    An explicit quantized ``algorithm`` (…_int8/…_fp8) names it directly;
    otherwise the wire knob (argument or ``HOROVOD_ALLREDUCE_WIRE``)
    supplies it when set to a quantized format."""
    from horovod_tpu import overlap as _overlap
    from horovod_tpu.config import get_config
    cfg = get_config()
    qw = _overlap.parse_algorithm(algorithm or cfg.allreduce_algorithm)[1]
    if qw is not None:
        return qw
    w = wire if wire is not None else cfg.allreduce_wire
    return w if w in _overlap.QUANT_WIRES else None


def _quantization_residual(tree: Any, wire: str) -> Any:
    """Per-leaf local quantization error ``x - dequantize(quantize(x))``
    (the error-feedback residual; EF-SGD / 1-bit Adam shape).

    This is the phase-1 error of THIS rank's contribution under the same
    block geometry the wire uses — the part of the gradient the quantized
    exchange drops on the floor locally. The re-quantization error of the
    reduced partial (phase 2) is shared by all ranks and ~1/k the size;
    it is deliberately not folded in (it is not locally attributable).
    Non-float leaves carry zero residuals."""
    from horovod_tpu.ops.quantized import dequantize_blocks, quantize_blocks

    def leaf(x):
        if not jnp.issubdtype(x.dtype, jnp.floating) or x.size == 0:
            return jnp.zeros_like(x)
        flat = x.ravel().astype(jnp.float32)
        q, s = quantize_blocks(flat, wire)
        return (flat - dequantize_blocks(q, s)).reshape(x.shape) \
            .astype(x.dtype)

    return jax.tree_util.tree_map(leaf, tree)


def reset_error_feedback(opt_state: Any) -> Any:
    """Zero every :class:`ErrorFeedbackState` residual in an optimizer
    state pytree (returns a new state).

    Called on elastic re-init (``elastic.JaxState.sync``): residuals are
    per-rank local error from the OLD communicator epoch — after a
    membership change they would re-inject another rank's stale error
    (the coordinator's state is broadcast to joiners), so they restart
    at zero like upstream resets its compression residuals."""

    def walk(node):
        if isinstance(node, ErrorFeedbackState):
            return ErrorFeedbackState(
                reset_error_feedback(node.inner),
                jax.tree_util.tree_map(jnp.zeros_like, node.residual))
        return node

    return jax.tree_util.tree_map(
        walk, opt_state,
        is_leaf=lambda n: isinstance(n, ErrorFeedbackState))


class AutotunedStep:
    """GP fusion autotuning for the JIT (optax) path — the consumer the
    r4 Bayesian tuner lacked (VERDICT r4 next #10; upstream
    ``horovod/runner/autotune`` tunes the running job the same way).

    The torch frontend feeds :class:`~horovod_tpu.autotune
    .BayesianAutotuner` from its eager dispatch loop, where the fusion
    threshold is a live runtime knob. In the jax path the threshold is a
    TRACE-TIME constant — ``DistributedOptimizer(fusion_threshold_bytes=
    ...)`` shapes the gradient bucketing inside the compiled program —
    so proposals can only take effect through recompilation. This
    wrapper owns that discipline:

    - ``make_step(threshold_bytes) -> step_fn`` builds (and jits) the
      training step for a given threshold; the optimizer state STRUCTURE
      is threshold-independent (bucketing only reshapes the allreduce),
      so state threads across rebuilds unchanged.
    - each call during tuning is timed with a blocking sync and fed to
      the tuner; when a probe completes, the proposal is agreed across
      processes (rank 0's point, the ``pending_sync`` contract) BEFORE
      it shapes a traced collective signature, and the step is rebuilt —
      one recompile per probe (6 by default), amortized over the run.
    - after convergence the winning program runs untimed (no sync, full
      dispatch overlap) for the rest of training.

    Usage::

        def make_step(threshold):
            opt = hvd.DistributedOptimizer(optax.adamw(1e-3),
                                           fusion_threshold_bytes=threshold)
            @jax.jit
            def step(params, opt_state, batch):
                ...
            return step

        step = hvd.AutotunedStep(make_step)
        for batch in data:
            params, opt_state = step(params, opt_state, batch)
    """

    def __init__(self, make_step, tuner=None):
        import inspect

        from horovod_tpu.autotune import BayesianAutotuner
        from horovod_tpu.config import get_config
        cfg = get_config()
        self._make = make_step
        # make_step(threshold) is the classic surface; a 3-arg
        # make_step(threshold, algorithm, chunks) additionally receives
        # the tuner's comm-algorithm picks (BayesianAutotuner(
        # tune_algorithm=True)) to thread into DistributedOptimizer.
        # Only REQUIRED positional params count — a 1-arg builder with
        # defaulted extras (make_step(thr, jit=True)) must not have an
        # algorithm string rammed into its keyword slots.
        try:
            sig = inspect.signature(make_step)
            self._make_arity = sum(
                1 for p in sig.parameters.values()
                if p.default is p.empty and p.kind in (
                    p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD))
        except (TypeError, ValueError):
            self._make_arity = 1
        self._tuner = tuner if tuner is not None else BayesianAutotuner(
            probes=cfg.autotune_probes,
            samples_per_probe=cfg.autotune_samples)
        self._fn = self._build(self._tuner.current_threshold())
        self._done = False
        # The first call after any (re)build pays jit trace + XLA compile
        # — recording it would hand the GP a compile-dominated outlier
        # (at small samples_per_probe the probe's median IS that
        # outlier). Run it untimed.
        self._skip_next = True

    @property
    def converged(self) -> bool:
        return self._done

    def current_threshold(self) -> int:
        return self._tuner.current_threshold()

    def _build(self, threshold: int):
        from horovod_tpu import profiler as _profiler
        if self._make_arity >= 3:
            t = self._tuner
            alg = getattr(t, "current_algorithm", lambda: "auto")()
            chunks = getattr(t, "current_chunks", lambda: None)()
            # Tuner rebuilds recompile BY DESIGN (one per probe);
            # expected=True keeps the count in recompiles_total{program}
            # without hvd.doctor() flagging the churn as a defect.
            if self._make_arity >= 4:
                # 4-arg builders additionally receive the wire-precision
                # pick (BayesianAutotuner(tune_wire=True)); compose into
                # DistributedOptimizer(algorithm=compose_algorithm(alg,
                # wire)) or pass wire= through hvd.allreduce.
                wire = getattr(t, "current_wire", lambda: "fp32")()
                _profiler.note_trace(
                    "autotuned_step",
                    {"fusion_threshold": str(int(threshold)),
                     "algorithm": str(alg), "chunks": str(chunks),
                     "wire": str(wire)},
                    expected=True)
                return self._make(threshold, alg, chunks, wire)
            _profiler.note_trace(
                "autotuned_step",
                {"fusion_threshold": str(int(threshold)),
                 "algorithm": str(alg), "chunks": str(chunks)},
                expected=True)
            return self._make(threshold, alg, chunks)
        _profiler.note_trace(
            "autotuned_step", {"fusion_threshold": str(int(threshold))},
            expected=True)
        return self._make(threshold)

    def _agree_and_rebuild(self) -> None:
        t = self._tuner
        if getattr(t, "pending_sync", False):
            # Proposals come from LOCAL timings; agree on rank 0's point
            # before it feeds any traced collective signature.
            if jax.process_count() > 1:
                t.set_current_point(tuple(C.broadcast_object(
                    t.current_point(), 0)))
            else:
                t.set_current_point(tuple(t.current_point()))
        if t.converged:
            best = int(t.current_threshold())
            if jax.process_count() > 1:
                # Each rank's argmin is over LOCAL timings; the compiled
                # program must use one agreed value — and the tuner must
                # REPORT that value (current_threshold() after
                # convergence is what users persist), so write it back.
                # The algorithm picks feed traced collective signatures
                # the same way; agree on rank 0's.
                best = int(C.broadcast_object(best, 0))
                t._best = best
                if getattr(t, "_tune_alg", False):
                    alg, chunks = C.broadcast_object(
                        (t.current_algorithm(), t.current_chunks()), 0)
                    t._best_algorithm, t._best_chunks = alg, int(chunks)
                if getattr(t, "_tune_wire", False):
                    t._best_wire = C.broadcast_object(t.current_wire(), 0)
                if getattr(t, "_tune_topology", False):
                    # The schedule pick rides current_algorithm()'s
                    # composed name too, but the reported pick must
                    # agree for summary()/persisted results.
                    t._best_topology = C.broadcast_object(
                        t.current_topology(), 0)
            self._fn = self._build(best)
            self._done = True
        else:
            self._fn = self._build(t.current_threshold())
        self._skip_next = True

    def __call__(self, *args, **kwargs):
        if self._done:
            return self._fn(*args, **kwargs)
        import time as _time
        if self._skip_next:
            out = self._fn(*args, **kwargs)
            jax.block_until_ready(out)   # absorb the compile untimed
            self._skip_next = False
            return out
        before = self._tuner.current_threshold()
        t0 = _time.perf_counter()
        out = self._fn(*args, **kwargs)
        jax.block_until_ready(out)   # honest step time while tuning
        dt = _time.perf_counter() - t0
        self._tuner.record(dt)
        # Step-time telemetry rides the tuning syncs for free; after
        # convergence the untimed path keeps full dispatch overlap, so the
        # gauge freezes at the last tuned-step value.
        _metrics.gauge("optimizer_step_seconds").set(dt)
        _metrics.histogram("optimizer_step_latency_seconds").observe(dt)
        from horovod_tpu import profiler as _profiler
        _profiler.observe_step("autotuned_step", dt)
        if (getattr(self._tuner, "pending_sync", False)
                or self._tuner.converged
                or self._tuner.current_threshold() != before):
            self._agree_and_rebuild()
        return out


def _set_grad_norm(v) -> None:
    _metrics.gauge("optimizer_grad_norm").set(float(v))


_GRAD_NORM_WARNED = False


def _maybe_record_grad_norm(grads) -> None:
    """Gradient-norm gauge (``HOROVOD_METRICS_GRAD_NORM=1``, off by
    default): global L2 norm of the float leaves. Under tracing the value
    reaches the host through ``jax.debug.callback`` — one tiny host
    callback per step, which is why it is opt-in."""
    from horovod_tpu.config import get_config
    if not get_config().metrics_grad_norm:
        return
    try:
        leaves = [g for g in jax.tree_util.tree_leaves(grads)
                  if hasattr(g, "dtype")
                  and jnp.issubdtype(g.dtype, jnp.floating)]
        if not leaves:
            return
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in leaves))
        if C._is_traced(norm):
            jax.debug.callback(_set_grad_norm, norm)
        else:
            _set_grad_norm(norm)
    except Exception:
        # Observability must never break the training step — but an
        # opted-in gauge that silently never records is a debugging trap;
        # say why, once.
        global _GRAD_NORM_WARNED
        if not _GRAD_NORM_WARNED:
            _GRAD_NORM_WARNED = True
            import logging
            logging.getLogger("horovod_tpu").warning(
                "HOROVOD_METRICS_GRAD_NORM is set but recording failed; "
                "optimizer_grad_norm will be absent", exc_info=True)


def allreduce_gradients(grads: Any, op: int = C.Average,
                        process_set: Optional[ProcessSet] = None,
                        compression=Compression.none,
                        prescale_factor: float = 1.0,
                        postscale_factor: float = 1.0,
                        fusion_threshold_bytes: Optional[int] = None,
                        alive: Optional[jnp.ndarray] = None,
                        algorithm: Optional[str] = None,
                        overlap_chunks: Optional[int] = None,
                        overlap: bool = False,
                        error_feedback: Any = None) -> Any:
    """Fused in-trace allreduce of gradients; skipped if already averaged.

    **Synchronised once.** Under ``hvd.spmd`` every plain pass marks the
    leaves it returns with what it gave (its op and process set), as does
    ``hvd.grad(overlap=True)``. A later pass is skipped, returning
    ``grads`` as they are, when *every* leaf of ``grads`` is such a
    marked object (the very object: anything computed from it, a clip, a
    scale, ``g + r``, a ``jit`` or ``cond`` boundary, is a new one), the
    mark and this pass both say ``Average`` over the same process set,
    and ``alive``, ``prescale_factor`` and ``postscale_factor`` are at
    their defaults: the average of equal values is that value. This is
    the README step, ``hvd.value_and_grad`` then
    ``DistributedOptimizer.update``, which used to reduce every gradient
    twice. ``Sum``, ``Adasum``, ``Min`` and ``Max`` are never skipped,
    nor is the result of an ``alive`` pass ever marked. ``compression``,
    ``algorithm``, ``overlap_chunks``, ``overlap`` and
    ``fusion_threshold_bytes`` say how a pass travels, not what it
    returns, so they do not keep a pass from being skipped: give them to
    the call that synchronises. The gauge
    ``grad_sync_skipped{program,scope}`` counts the passes skipped.
    Outside ``hvd.spmd`` (a ``shard_map`` of your own) nothing is marked
    and every pass lowers.

    ``alive`` implements the Join op for uneven data (upstream
    ``horovod/common/ops/../join``): pass a 0/1 scalar per device; dead
    devices contribute zeros and the mean divides by the live count.

    ``algorithm`` / ``overlap_chunks`` select the per-bucket lowering
    (see :func:`horovod_tpu.collective.allreduce`). ``overlap=True``
    issues the per-bucket collectives in reverse bucket order with
    pinned scheduling (``lax.optimization_barrier``) — the last-produced
    gradients' bucket goes first, so the latency-hiding scheduler can
    start it while earlier layers are still in their backward — instead
    of one ordering-free batch at the end of backward. For collectives
    issued *inside* the backward itself use ``hvd.grad(overlap=True)``
    (custom_vjp taps).

    ``error_feedback`` (a residual pytree shaped like ``grads``, zeros
    at step 0) turns on error-feedback compensation for the quantized
    wire formats: the residual from step t is added into the gradients
    before synchronization, and the local quantization error of the
    compensated gradients becomes the step-t+1 residual — so the error
    the 1-byte wire drops is re-injected instead of lost, which is what
    makes quantized-wire training converge to the fp32 loss curve.
    Returns ``(synced_grads, new_residual)`` instead of just the grads.
    With no quantized wire in effect the residual stays zero and the
    synchronization is unchanged. Held for you by
    ``DistributedOptimizer(error_feedback=True)``.
    """
    if error_feedback is not None:
        qwire = _effective_quant_wire(algorithm)
        if qwire is None:
            out = allreduce_gradients(
                grads, op=op, process_set=process_set,
                compression=compression, prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                fusion_threshold_bytes=fusion_threshold_bytes,
                alive=alive, algorithm=algorithm,
                overlap_chunks=overlap_chunks, overlap=overlap)
            return out, jax.tree_util.tree_map(jnp.zeros_like,
                                               error_feedback)
        compensated = jax.tree_util.tree_map(
            lambda g, r: g + r.astype(g.dtype), grads, error_feedback)
        out = allreduce_gradients(
            grads=compensated, op=op, process_set=process_set,
            compression=compression, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            fusion_threshold_bytes=fusion_threshold_bytes, alive=alive,
            algorithm=algorithm, overlap_chunks=overlap_chunks,
            overlap=overlap)
        if not core.in_spmd_context():
            # jit auto-sharding: XLA reduced exactly; nothing was lost.
            return out, jax.tree_util.tree_map(jnp.zeros_like,
                                               error_feedback)
        return out, _quantization_residual(compensated, qwire)
    if not core.in_spmd_context():
        # jit auto-sharding mode: XLA already reduced the grads.
        _maybe_record_grad_norm(grads)
        return grads
    comm_kw = dict(compression=compression,
                   fusion_threshold_bytes=fusion_threshold_bytes,
                   algorithm=algorithm, overlap_chunks=overlap_chunks,
                   _reverse_issue=overlap)
    # The sync manifest (tracing.py) counts what this pass hands to
    # all-reduce, under the caller's scope; trace time only.
    ps = C._resolve_ps(process_set)
    peers = ps.size()
    if alive is not None:
        if op not in (C.Average, C.Sum):
            raise ValueError("join-style allreduce supports Sum/Average only")
        alivef = jnp.asarray(alive, jnp.float32)
        n_alive = C.allreduce(alivef, op=C.Sum, process_set=process_set)
        n_alive = jnp.maximum(n_alive, 1.0)
        grads = jax.tree_util.tree_map(
            lambda g: g * alivef.astype(g.dtype), grads)
        with _tracing.sync_pass(peers):
            summed = C.allreduce(grads, op=C.Sum, process_set=process_set,
                                 prescale_factor=prescale_factor,
                                 postscale_factor=postscale_factor,
                                 **comm_kw)
        if op == C.Average:
            summed = jax.tree_util.tree_map(
                lambda g: g / n_alive.astype(g.dtype), summed)
        _maybe_record_grad_norm(summed)
        return summed
    if (op == C.Average and prescale_factor == 1.0
            and postscale_factor == 1.0
            and _tracing.synced_as(grads) == (C.Average, ps)):
        # Every leaf is the very object an earlier pass of this trace
        # returned as the average over this process set: the average of
        # equal values is that value, so this pass lowers nothing. The
        # scope keeps its manifest entry, counted as skipped.
        with _tracing.sync_pass(peers, skipped=True):
            pass
        _maybe_record_grad_norm(grads)
        return grads
    with _tracing.sync_pass(peers):
        out = C.allreduce(grads, op=op, process_set=process_set,
                          prescale_factor=prescale_factor,
                          postscale_factor=postscale_factor, **comm_kw)
    _tracing.mark_synced(out, (op, ps))
    _maybe_record_grad_norm(out)
    return out


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         op: int = C.Average,
                         process_set: Optional[ProcessSet] = None,
                         compression=Compression.none,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0,
                         fusion_threshold_bytes: Optional[int] = None,
                         backward_passes_per_step: int = 1,
                         algorithm: Optional[str] = None,
                         overlap_chunks: Optional[int] = None,
                         overlap: bool = False,
                         error_feedback: Optional[bool] = None,
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer: ``update`` syncs gradients not yet averaged.

    ``hvd.DistributedOptimizer``: gradients are synchronized before the
    inner update. Use inside the jitted, shard_mapped train step; with jit
    auto-sharding it degrades to the inner optimizer unchanged.

    Handed the gradients ``hvd.value_and_grad`` / ``hvd.grad`` /
    ``hvd.allreduce_gradients`` returned in the same ``hvd.spmd`` step,
    untouched, an ``op=Average`` optimizer lowers no second pass (see
    :func:`allreduce_gradients`, "Synchronised once"): the update runs on
    the first pass's values, and this wrapper's ``compression``,
    ``algorithm``, ``overlap_chunks``, ``overlap`` and
    ``fusion_threshold_bytes`` then shape nothing. Wire options belong on
    the call that synchronises; to have them apply here, take the
    gradients from plain ``jax.value_and_grad``.

    ``backward_passes_per_step=k`` mirrors the upstream argument (local
    gradient accumulation: one allreduce per k backward passes, the
    accumulated gradients *summed* before synchronisation, exactly
    upstream's semantics — same LR transfers). The JAX shape is
    ``optax.MultiSteps`` around the synchronized transform (with a
    rescale-by-k to turn its running mean back into the upstream sum) —
    ``update`` returns zero updates on the k-1 accumulation steps and the
    synced update on every k-th; everything stays jit-compatible (counter +
    accumulator live in the optimizer state; probe the k-boundary with
    ``accumulation_has_updated(opt_state)``).

    ``algorithm`` / ``overlap_chunks`` select the per-bucket allreduce
    lowering (``psum`` / ``rs_ag`` / ``chunked_rs_ag`` / the quantized
    ``…_int8``/``…_fp8`` variants / ``auto``; see
    :func:`horovod_tpu.collective.allreduce`); ``overlap=True`` issues
    per-bucket collectives in reverse production order with pinned
    scheduling instead of one end-of-backward batch (see
    :func:`allreduce_gradients`).

    ``error_feedback`` carries the quantized wire's per-parameter
    residual across steps (:class:`ErrorFeedbackState` wraps the inner
    optimizer state; see :func:`allreduce_gradients`). The default
    (``None``) enables it automatically when the resolved algorithm —
    the argument, or ``HOROVOD_ALLREDUCE_ALGORITHM`` when omitted —
    explicitly names a quantized wire: training on a 1-byte wire without
    error feedback drifts, so the safe pairing is the default. Pass ``False``
    to measure the uncompensated drift, ``True`` to force it on (e.g.
    when ``HOROVOD_ALLREDUCE_WIRE=int8`` routes quantization through
    ``auto``; note the residual is then an approximation on buckets that
    resolve to the exact psum). Residuals restart at zero on elastic
    re-init (:func:`reset_error_feedback`).
    """
    if error_feedback is None:
        # Resolved at wrap time (the state STRUCTURE depends on it): the
        # argument, or the env-configured algorithm when no argument —
        # HOROVOD_ALLREDUCE_ALGORITHM=chunked_rs_ag_int8 must not train
        # uncompensated just because the kwarg was omitted.
        from horovod_tpu import overlap as _overlap
        from horovod_tpu.config import get_config
        resolved = (algorithm if algorithm is not None
                    else get_config().allreduce_algorithm)
        error_feedback = _overlap.parse_algorithm(resolved)[1] is not None

    def init(params):
        if error_feedback:
            return ErrorFeedbackState(
                optimizer.init(params),
                jax.tree_util.tree_map(jnp.zeros_like, params))
        return optimizer.init(params)

    def update(grads, state, params=None, **extra):
        sync_kw = dict(
            op=op, process_set=process_set, compression=compression,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            fusion_threshold_bytes=fusion_threshold_bytes,
            alive=extra.pop("alive", None), algorithm=algorithm,
            overlap_chunks=overlap_chunks, overlap=overlap)
        if error_feedback:
            inner_state, residual = state
            with _tracing.scope("hvd/optimizer/sync"):
                grads, residual = allreduce_gradients(
                    grads, error_feedback=residual, **sync_kw)
            with _tracing.scope("hvd/optimizer/update"):
                updates, inner_state = optimizer.update(
                    grads, inner_state, params, **extra)
            return updates, ErrorFeedbackState(inner_state, residual)
        with _tracing.scope("hvd/optimizer/sync"):
            grads = allreduce_gradients(grads, **sync_kw)
        with _tracing.scope("hvd/optimizer/update"):
            return optimizer.update(grads, state, params, **extra)

    tx = optax.GradientTransformation(init, update)
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1, got "
                         f"{backward_passes_per_step}")
    if backward_passes_per_step > 1:
        # MultiSteps feeds the *mean* of the k accumulated gradients to its
        # inner transform; upstream sums before the allreduce. Scale by k so
        # a learning rate tuned on upstream transfers unchanged.
        k = float(backward_passes_per_step)
        tx = optax.chain(optax.scale(k), tx)
        ms = optax.MultiSteps(tx, every_k_schedule=backward_passes_per_step)
        tx = optax.GradientTransformation(ms.init, ms.update)
    return tx


def accumulation_has_updated(opt_state) -> "jnp.ndarray":
    """True when the last ``update`` on a ``backward_passes_per_step > 1``
    optimizer applied a real step (the k-th pass) rather than accumulating.
    Use to gate LR-schedule advances or per-step logging."""
    return optax.MultiSteps(optax.identity(), 1).has_updated(opt_state)


def grad(fun: Callable, argnums=0, op: int = C.Average,
         process_set: Optional[ProcessSet] = None,
         compression=Compression.none, overlap: bool = False,
         algorithm: Optional[str] = None,
         overlap_chunks: Optional[int] = None, **gradkw) -> Callable:
    """Distributed ``jax.grad``: gradients are allreduced across the
    communicator (the JAX-native ``hvd.DistributedGradientTape``).

    ``overlap=True`` swaps the end-of-backward allreduce for custom_vjp
    identity taps on each top-level parameter group
    (:func:`horovod_tpu.overlap.tap_params`): every group's gradient is
    synchronized *inside* the backward, the moment it is produced —
    reverse production order for free — so XLA overlaps the collectives
    with the rest of the backward instead of serializing them after it.
    """
    if overlap:
        from horovod_tpu import overlap as _overlap
        sync_kw = dict(op=op, process_set=process_set,
                       compression=compression, algorithm=algorithm,
                       overlap_chunks=overlap_chunks)
        idxs = (argnums,) if isinstance(argnums, int) else tuple(argnums)

        def tapped_fun(*args, **kwargs):
            args = list(args)
            for i in idxs:
                args[i] = _overlap.tap_params(args[i], **sync_kw)
            return fun(*args, **kwargs)

        gfun = jax.grad(tapped_fun, argnums=argnums, **gradkw)

        def wrapped(*args, **kwargs):
            g = gfun(*args, **kwargs)
            # The taps already synchronized every group; only telemetry
            # remains, and the mark that lets a later pass see it
            # (outside an SPMD context the taps are identities).
            if core.in_spmd_context():
                _tracing.mark_synced(g, (op, C._resolve_ps(process_set)))
            _maybe_record_grad_norm(g)
            return g
        return wrapped

    gfun = jax.grad(fun, argnums=argnums, **gradkw)

    def wrapped(*args, **kwargs):
        g = gfun(*args, **kwargs)
        with _tracing.scope("hvd/grad/sync"):
            return allreduce_gradients(g, op=op, process_set=process_set,
                                       compression=compression,
                                       algorithm=algorithm,
                                       overlap_chunks=overlap_chunks)
    return wrapped


def value_and_grad(fun: Callable, argnums=0, op: int = C.Average,
                   process_set: Optional[ProcessSet] = None,
                   compression=Compression.none, **gradkw) -> Callable:
    """Distributed ``jax.value_and_grad``: the README step's one gradient sync.

    The value is also averaged so every device reports the global loss
    (matches DistributedGradientTape + MetricAverageCallback behaviour).

    This is the pass that travels when its gradients go on, untouched, to
    an ``op=Average`` :func:`DistributedOptimizer` in the same ``hvd.spmd``
    step: the optimizer's own pass is then skipped
    (:func:`allreduce_gradients`), so ``compression`` belongs here."""
    vgfun = jax.value_and_grad(fun, argnums=argnums, **gradkw)

    def wrapped(*args, **kwargs):
        v, g = vgfun(*args, **kwargs)
        if core.in_spmd_context():
            v = jax.tree_util.tree_map(
                lambda x: C.allreduce(x, op=C.Average,
                                      process_set=process_set), v)
        with _tracing.scope("hvd/value_and_grad/sync"):
            g = allreduce_gradients(g, op=op, process_set=process_set,
                                    compression=compression)
        return v, g
    return wrapped


class DistributedGradientTape:
    """API-parity shim for TF2 users (upstream
    ``horovod/tensorflow/__init__.py:DistributedGradientTape``): records a
    loss function and returns synchronized gradients."""

    def __init__(self, op: int = C.Average,
                 process_set: Optional[ProcessSet] = None,
                 compression=Compression.none):
        self._op = op
        self._ps = process_set
        self._comp = compression

    def gradient(self, fun: Callable, params, *args, **kwargs):
        g = jax.grad(fun)(params, *args, **kwargs)
        with _tracing.scope("hvd/tape/sync"):
            return allreduce_gradients(g, op=self._op,
                                       process_set=self._ps,
                                       compression=self._comp)


def broadcast_parameters(params: Any, root_rank: int = 0,
                         process_set: Optional[ProcessSet] = None) -> Any:
    """Synchronize a parameter pytree from ``root_rank``
    (``hvd.broadcast_parameters`` / ``broadcast_global_variables``).

    In-trace this is a real psum-based broadcast; eagerly on a single
    controller parameters are already globally consistent, so it is an
    identity (multi-process eager uses the object broadcast path).
    """
    if any(isinstance(x, jax.core.Tracer)
           for x in jax.tree_util.tree_leaves(params)):
        return C.broadcast(params, root_rank, process_set=process_set)
    if jax.process_count() > 1:
        # root_rank is a global *device* rank; the host-side object broadcast
        # sources from the process that owns that device.
        root_proc = int(root_rank) // jax.local_device_count()
        return C.broadcast_object(params, root_proc)
    return params


def broadcast_variables(variables: Any, root_rank: int = 0, **kw) -> Any:
    return broadcast_parameters(variables, root_rank, **kw)


def broadcast_optimizer_state(opt_state: Any, root_rank: int = 0,
                              process_set: Optional[ProcessSet] = None) -> Any:
    """``hvd.broadcast_optimizer_state`` for optax states."""
    return broadcast_parameters(opt_state, root_rank, process_set=process_set)
