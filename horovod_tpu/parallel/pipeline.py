"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

SURVEY §2 row 26. The reference ecosystem layers pipeline engines (DeepSpeed/
Megatron) on top of hvd's p2p; here the pipeline is a first-class program:
stages live on devices along the ``pp`` mesh axis, activations hop stage to
stage with ``lax.ppermute`` (one ICI neighbour-hop per tick — the cheapest
possible transfer on a torus), and the whole schedule is a single
``lax.scan`` that XLA compiles into a static loop. Backward works by
autodiff: the transpose of ppermute is the reverse ppermute, so the backward
pipeline (reverse hops) is derived — no hand-written 1F1B engine needed for
correctness.

Schedule / bubble cost
----------------------
With ``S`` stages and ``M`` microbatches the scan runs ``T = M + S - 1``
ticks; each device computes for ``M`` of them, so the bubble (idle) fraction
is ``(S - 1) / (M + S - 1)`` — identical to GPipe's fill/drain bubble.
Picking ``M``:

===========  ==========================
M / (S-1)    bubble fraction
===========  ==========================
1            50 %
3            25 %
7            12.5 %
15           6.25 %
===========  ==========================

i.e. use ``M >= 4*(S-1)`` to keep the bubble under ~20 %. Memory grows
linearly in ``M`` (the scan saves each tick's stage activations for the
backward pass, which is exactly GPipe's per-microbatch stashing), so ``M``
trades bubble against HBM the same way it does upstream. When that stash
does not fit, use :func:`pipeline_1f1b` — a hand-scheduled forward+backward
schedule whose stash is a ring buffer of ``min(2S-1, M)`` in-flight
microbatches (O(S), independent of M), the TPU analogue of the 1F1B
schedules the reference ecosystem layers on hvd p2p (Megatron/DeepSpeed).

Training: use :func:`pipeline_loss`, which computes the caller's loss on the
**last stage only** (masked before the cross-stage psum) so gradients are
correct with no caller-side scaling. :func:`pipeline_apply` is the
forward/inference variant that broadcasts the final outputs to every stage.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pipeline_apply", "pipeline_loss", "pipeline_loss_interleaved",
           "pipeline_1f1b", "pipeline_interleaved_1f1b", "chunkable_loss"]


def _graft_last_stage_loss(local, is_last, axis_name):
    """Forward: replicate the last stage's loss via psum. Backward: a
    psum's transpose would re-psum every stage's unit cotangent (an S×
    factor), so the replicated value is grafted on with stop_gradient and
    only the masked per-stage copy is differentiated — the last stage
    seeds the backward pipeline, earlier stages receive their cotangents
    through the transposed ppermute hops."""
    masked = jnp.where(is_last, local, jnp.zeros_like(local))
    return masked + lax.stop_gradient(lax.psum(masked, axis_name) - masked)


def _run_pipeline(stage_fn: Callable, stage_params: Any,
                  microbatches: jnp.ndarray, axis_name: str):
    """Shared GPipe scan. Returns (outputs, stage_index, num_stages) where
    ``outputs`` is (M, mb, ...) — valid only on the last stage (zeros
    elsewhere)."""
    S = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    T = M + S - 1                       # total ticks incl. fill/drain bubble
    mb_shape = microbatches.shape[1:]

    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        act_in, outputs = carry
        # Stage 0 feeds microbatch t (clamped; masked when t >= M).
        feed_idx = jnp.clip(t, 0, M - 1)
        feed = lax.dynamic_index_in_dim(microbatches, feed_idx, 0,
                                        keepdims=False)
        x = jnp.where(stage == 0, feed, act_in)
        y = stage_fn(stage_params, x)
        # Last stage emits microbatch t-(S-1) when in the valid window.
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        valid = (t >= S - 1) & (stage == S - 1)
        cur = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, y, cur), out_idx, 0)
        # Hop to the next stage; stage 0 receives zeros (overwritten by feed).
        act_next = lax.ppermute(y, axis_name, fwd_perm)
        return (act_next, outputs), None

    act0 = jnp.zeros(mb_shape, microbatches.dtype)
    out0 = jnp.zeros((M,) + mb_shape, microbatches.dtype)
    act0, out0 = _vary_over(axis_name, act0, out0)
    (_, outputs), _ = lax.scan(tick, (act0, out0), jnp.arange(T))
    return outputs, stage, S


def _vary_over(axis_name: str, *xs):
    """Mark fresh zeros as varying over the pipe axis: under a multi-axis
    ``shard_map`` the scan carry's output is pp-varying (ppermute), and jax
    requires the initial carry to match (vma typing)."""
    return tuple(lax.pcast(x, (axis_name,), to="varying") for x in xs)


def pipeline_apply(stage_fn: Callable, stage_params: Any,
                   microbatches: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Run ``stage_fn`` as a pipeline over the ``axis_name`` mesh axis
    (forward / inference path).

    Call inside ``shard_map``. Device ``s`` holds ``stage_params`` for stage
    ``s`` (same pytree structure on every stage, e.g. a slice of stacked
    layer params).

    Args:
      stage_fn: ``(stage_params, x) -> y`` with ``y.shape == x.shape``
        (standard transformer-block contract).
      stage_params: this device's stage parameters.
      microbatches: (M, mb, ...) — the full microbatched input, replicated
        across the axis (only stage 0 reads it).
      axis_name: the ``pp`` mesh axis.

    Returns (M, mb, ...): the pipeline output for all microbatches, valid on
    the *last* stage and broadcast to all stages.

    Training note: the broadcast replicates the outputs, so a loss built from
    them feeds the transposed psum on backward with an extra factor ``S`` —
    use :func:`pipeline_loss` for training instead of scaling by hand.
    """
    outputs, stage, S = _run_pipeline(stage_fn, stage_params, microbatches,
                                      axis_name)
    # Broadcast the last stage's outputs to every stage (psum of one-hot).
    return lax.psum(
        jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)


def pipeline_loss(stage_fn: Callable, stage_params: Any,
                  microbatches: jnp.ndarray, loss_fn: Callable,
                  axis_name: str) -> jnp.ndarray:
    """Pipeline forward + loss with **correct gradients** (training path).

    ``loss_fn(outputs) -> scalar`` is evaluated on the pipeline outputs
    (M, mb, ...) and masked to the last stage *before* the cross-stage psum,
    so each parameter's cotangent flows exactly once — no ``1/S`` caller
    scaling. The returned scalar is replicated across stages.

    Notes:
      * ``loss_fn`` runs on every stage (SPMD: the mask is a select, not a
        branch) but only the last stage's value/gradient survives. It must
        therefore be finite on all-zero inputs (non-last stages see zeros);
        standard log-softmax/MSE losses are.
      * ``loss_fn`` may close over replicated per-microbatch targets; their
        gradient contributions are zero off the last stage, so psum-ing
        parameter grads over the pipe axis (the usual replicated-param rule)
        gives the correct totals.
    """
    outputs, stage, S = _run_pipeline(stage_fn, stage_params, microbatches,
                                      axis_name)
    local = (loss_fn(outputs, 0) if _loss_takes_start(loss_fn)
             else loss_fn(outputs))
    return _graft_last_stage_loss(local, stage == S - 1, axis_name)


def pipeline_loss_interleaved(stage_fn: Callable, stage_params: Any,
                              microbatches: jnp.ndarray, loss_fn: Callable,
                              axis_name: str) -> jnp.ndarray:
    """Interleaved (circular) pipeline schedule + loss (Megatron's
    interleaved 1F1B layout, expressed as one scan).

    Device ``d`` holds ``R`` *virtual stages* — rounds ``r = 0..R-1`` of the
    depth-``R*S`` pipeline, virtual stage ``sigma = r*S + d`` — as the
    leading axis of ``stage_params`` (shape ``(R, ...)`` per device).
    Activations hop device-to-device on a wrapped ring: after stage
    ``r*S + S-1`` the microbatch re-enters device 0 at round ``r+1``.

    Why: the bubble is ``1 - R*M / (M + R*S - 1)``; at ``M = S`` that is
    ``~1/(R+1)`` — e.g. 20 % at R=4 with only S microbatches in flight,
    where plain GPipe needs ``M = 4*(S-1)`` microbatches (4x the activation
    memory) for the same bubble.

    Ring constraint + automatic chunking: at most ``S`` microbatches fit
    on the wrapped ring at once. ``M > S`` is handled by chunking the
    microbatches into ``ceil(M/S)`` sub-schedules and accumulating — the
    total is the microbatch-count-weighted mean of chunk losses, which
    equals the full-batch loss when ``loss_fn`` is a mean over the
    microbatch axis (autodiff accumulates the grads). Chunking needs the
    two-argument loss form (below) so targets follow their microbatches.

    ``loss_fn(outputs) -> scalar`` is evaluated on (M, mb, ...) outputs,
    masked to the final virtual stage's device exactly like
    :func:`pipeline_loss`. A two-argument ``loss_fn(outputs, mb_start)``
    is also accepted (required for chunking): ``mb_start`` is the static
    index of ``outputs[0]`` in the full microbatch sequence, letting the
    loss slice its closed-over targets.
    """
    S = lax.psum(1, axis_name)
    d = lax.axis_index(axis_name)
    R = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    M = microbatches.shape[0]
    if M > S:
        if not _loss_takes_start(loss_fn):
            raise ValueError(
                f"interleaved schedule fits at most S={S} microbatches on "
                f"the ring at once; chunking the given M={M} automatically "
                f"needs a loss_fn(outputs, mb_start) so targets can follow "
                f"their chunk. Name the second positional 'mb_start', or —"
                f" for functools.partial / C callables whose signature "
                f"cannot be inspected — mark the loss with "
                f"horovod_tpu.parallel.chunkable_loss")
        def chunk_loss(start):
            # unary on purpose: the recursive call must not re-chunk it
            return lambda outs: loss_fn(outs, start)

        total = jnp.float32(0.0)
        for start in range(0, M, S):
            chunk = microbatches[start:start + S]
            total = total + (chunk.shape[0] / M) * pipeline_loss_interleaved(
                stage_fn, stage_params, chunk, chunk_loss(start), axis_name)
        return total
    T = M + R * S - 1
    mb_shape = microbatches.shape[1:]

    ring = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        act_in, outputs = carry
        rel = t - d
        r = jnp.clip(jnp.where(rel >= 0, rel // S, 0), 0, R - 1)
        active = (rel >= 0) & (rel < R * S) & ((rel % S) < M)
        # Device 0, round 0 feeds microbatch m = t (while t < M).
        feed_idx = jnp.clip(t, 0, M - 1)
        feed = lax.dynamic_index_in_dim(microbatches, feed_idx, 0,
                                        keepdims=False)
        x = jnp.where((d == 0) & (rel < M), feed, act_in)
        params_r = jax.tree_util.tree_map(
            lambda p: lax.dynamic_index_in_dim(p, r, 0, keepdims=False),
            stage_params)
        y = stage_fn(params_r, x)
        # Final virtual stage (device S-1, round R-1) emits m = t-(R*S-1).
        out_idx = jnp.clip(t - (R * S - 1), 0, M - 1)
        emit = active & (d == S - 1) & (rel // S == R - 1)
        cur = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(emit, y, cur), out_idx, 0)
        act_next = lax.ppermute(y, axis_name, ring)
        return (act_next, outputs), None

    act0 = jnp.zeros(mb_shape, microbatches.dtype)
    out0 = jnp.zeros((M,) + mb_shape, microbatches.dtype)
    (_, outputs), _ = lax.scan(tick, (act0, out0), jnp.arange(T))

    local = (loss_fn(outputs, 0) if _loss_takes_start(loss_fn)
             else loss_fn(outputs))
    return _graft_last_stage_loss(local, d == S - 1, axis_name)


def _mb_loss_cond(per_mb_loss, loss_params, y, m, M, pred):
    """Loss-head vjp under ``lax.cond`` — shared by BOTH 1F1B executors so
    the head's scaling/dtype contract has one definition: fires only when
    ``pred`` (a live last-stage slot), seeds the cotangent with ``1/M``,
    returns ``(loss_f32, g_loss_params, gy)`` (zeros when gated off)."""

    def _loss_slot(args):
        lp, yy, mm = args
        l, l_vjp = jax.vjp(
            lambda lp_, yy_: per_mb_loss(lp_, yy_, mm), lp, yy)
        g_lp, gy = l_vjp(jnp.asarray(1.0 / M, l.dtype))
        return l.astype(jnp.float32), g_lp, gy.astype(yy.dtype)

    def _no_loss(args):
        lp, yy, _ = args
        return (jnp.float32(0.0),
                jax.tree_util.tree_map(jnp.zeros_like, lp),
                jnp.zeros_like(yy))

    return lax.cond(pred, _loss_slot, _no_loss, (loss_params, y, m))


def chunkable_loss(loss_fn):
    """Explicitly mark ``loss_fn`` as taking the two-argument
    ``(outputs, mb_start)`` chunking form.

    The chunking schedules (``pipeline_loss_interleaved`` with ``M > S``)
    detect the two-argument form by signature, which cannot see through
    ``functools.partial`` or C-accelerated callables — wrap those with this
    marker::

        loss = hvd.parallel.chunkable_loss(functools.partial(f, cfg))

    Plain ``def loss(outputs, mb_start)`` needs no marker (the parameter
    name is recognised).
    """
    try:
        loss_fn._hvd_mb_start = True
        return loss_fn
    except (AttributeError, TypeError):   # builtins reject attributes
        @functools.wraps(loss_fn, assigned=("__doc__",), updated=())
        def wrapped(outputs, mb_start):
            return loss_fn(outputs, mb_start)
        wrapped._hvd_mb_start = True
        return wrapped


def _loss_takes_start(loss_fn) -> bool:
    """Does ``loss_fn`` accept the two-argument ``(outputs, mb_start)``
    chunking form?

    True iff the loss is marked via :func:`chunkable_loss` or its second
    positional parameter is literally named ``mb_start``. A merely-binary
    signature does NOT opt in: ``loss(outputs, weights)`` must fail loudly
    (TypeError at call) rather than silently receive an index where data
    was expected.
    """
    if getattr(loss_fn, "_hvd_mb_start", False):
        return True
    import inspect
    try:
        params = inspect.signature(loss_fn).parameters.values()
    except (TypeError, ValueError):
        return False
    positional = [p for p in params if p.kind in
                  (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    return len(positional) >= 2 and positional[1].name == "mb_start"


# ---------------------------------------------------------------------------
# 1F1B: hand-scheduled backward with an O(S) activation stash
# ---------------------------------------------------------------------------

def _x_dependent_leaf_mask(stage_fn, stage_params, x_struct):
    """Which leaves of ``jax.vjp(stage_fn, p, x)[1]`` (a flattenable
    ``Partial`` pytree) depend on ``x``?

    Param-only residual leaves (e.g. the weight a matmul transpose reads)
    are identical every microbatch, so ring-stashing them would duplicate
    the stage weights ``O(S)`` times; the 1F1B scan instead takes them from
    the current tick's vjp and stashes only the x-dependent leaves. The
    test is a conservative taint walk over the jaxpr: a leaf is "dependent"
    if any path from the x invars reaches it (over-approximation only ever
    stashes more, never corrupts)."""
    from jax.extend import core as jcore

    def residuals(p, xx):
        return jax.tree_util.tree_leaves(jax.vjp(stage_fn, p, xx)[1])

    p_struct = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), stage_params)
    closed = jax.make_jaxpr(residuals)(p_struct, x_struct)
    jaxpr = closed.jaxpr
    n_p = len(jax.tree_util.tree_leaves(stage_params))
    tainted = set(jaxpr.invars[n_p:])
    for eqn in jaxpr.eqns:
        if any(isinstance(v, jcore.Var) and v in tainted
               for v in eqn.invars):
            tainted.update(eqn.outvars)
    return [isinstance(ov, jcore.Var) and ov in tainted
            for ov in jaxpr.outvars]


def pipeline_1f1b(stage_fn: Callable, per_mb_loss: Callable,
                  axis_name: str) -> Callable:
    """Build a 1F1B pipeline step: hand-scheduled forward AND backward in
    one ``lax.scan``, activation stash bounded at ``min(2S-1, M)``
    microbatches per device instead of GPipe-under-autodiff's ``M + S - 1``
    per-tick residual sets.

    Reference parity: this is the role of the 1F1B/PipeDream-flush
    schedules the reference ecosystem (Megatron-LM, DeepSpeed) layers on
    horovod p2p sends. TPU-first shape: the schedule is a single compiled
    scan of masked F and B slots in lock-step — device ``s`` runs the
    forward of microbatch ``t - s`` and the backward of microbatch
    ``t - 2(S-1) + s`` at tick ``t``; activations hop forward and
    cotangents hop backward with one ``lax.ppermute`` ICI-neighbour step
    per tick. No recompute: the per-microbatch vjp residuals are stashed
    in a ring buffer, with param-only residual leaves (stage weights)
    deduplicated via :func:`_x_dependent_leaf_mask` so the ring holds only
    x-dependent activations.

    Args:
      stage_fn: ``(stage_params, x) -> y`` with ``y.shape == x.shape``.
      per_mb_loss: ``(loss_params, y, m) -> scalar`` — microbatch ``m``'s
        loss contribution given the last stage's output ``y``; the total
        loss is the MEAN over microbatches (so a per-microbatch mean loss
        composes to the same value as a full-batch mean). It may index
        closed-over targets with the traced ``m``. It must NOT contain
        collectives: it runs under a ``lax.cond`` that fires only on the
        last stage's live slots (so the loss head's FLOPs are paid M
        times on one stage, not ``M + 2(S-1)`` times on every stage),
        and cond predicates differ across devices.
      axis_name: the ``pp`` mesh axis.

    Returns ``fn(stage_params, loss_params, microbatches) ->
    (loss, (g_stage, g_loss_params, g_microbatches))`` for use inside
    ``shard_map``; no outer ``jax.grad`` — the backward IS the schedule.
    ``loss`` is returned ALREADY replicated across stages (do not psum it
    again — that would multiply it by S). ``g_loss_params`` is nonzero on
    the last stage only and ``g_microbatches`` on stage 0 only (psum those
    over ``axis_name`` to replicate — they are zero elsewhere, so the psum
    is a broadcast); ``g_stage`` is stage-local like the params themselves.
    """

    def fn(stage_params, loss_params, microbatches):
        S = lax.psum(1, axis_name)
        stage = lax.axis_index(axis_name)
        M = microbatches.shape[0]
        mb_shape = microbatches.shape[1:]
        dtype = microbatches.dtype
        W = min(2 * S - 1, M)
        T = M + 2 * (S - 1)

        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        bwd_perm = [(i + 1, i) for i in range(S - 1)]

        x_struct = jax.ShapeDtypeStruct(mb_shape, dtype)
        dep_mask = _x_dependent_leaf_mask(stage_fn, stage_params, x_struct)
        res_structs = jax.eval_shape(
            lambda p, xx: jax.tree_util.tree_leaves(
                jax.vjp(stage_fn, p, xx)[1]),
            stage_params, x_struct)

        def tick(carry, t):
            act_in, cot_in, ring, g_stage, g_loss, g_x, loss_acc = carry

            # ---- F slot: forward of microbatch t - stage
            m_f = t - stage
            active_f = (m_f >= 0) & (m_f < M)
            feed = lax.dynamic_index_in_dim(
                microbatches, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            x = jnp.where(stage == 0, feed, act_in)
            y, vjp_fn = jax.vjp(stage_fn, stage_params, x)
            cur_leaves, res_treedef = jax.tree_util.tree_flatten(vjp_fn)
            slot_f = jnp.remainder(jnp.clip(m_f, 0, M - 1), W)
            new_ring = []
            for r, leaf, dep in zip(ring, cur_leaves, dep_mask):
                if not dep:
                    new_ring.append(r)      # param-only: never stashed
                    continue
                old = lax.dynamic_index_in_dim(r, slot_f, 0, keepdims=False)
                new_ring.append(lax.dynamic_update_index_in_dim(
                    r, jnp.where(active_f, leaf, old), slot_f, 0))
            ring = new_ring

            # ---- B slot: backward of microbatch t - 2(S-1) + stage
            m_b = t - 2 * (S - 1) + stage
            active_b = (m_b >= 0) & (m_b < M)
            mb_idx = jnp.clip(m_b, 0, M - 1)
            # Last stage: seed cotangent from THIS tick's forward output
            # (at stage S-1, m_b == m_f, and its residuals were just
            # written). The loss head (for GPT-2: fp32 LN + the
            # (mb,T,d)x(V,d) logits einsum) is gated behind lax.cond so
            # its FLOPs burn only on the last stage's M live slots — not
            # T = M + 2(S-1) times on every stage as a masked select
            # would (r3 weak 3). per_mb_loss must therefore contain no
            # collectives: the predicate differs across devices.
            is_loss_slot = active_b & (stage == S - 1)
            l, g_lp_m, gy_seed = _mb_loss_cond(
                per_mb_loss, loss_params, y, mb_idx, M, is_loss_slot)
            g_in = jnp.where(stage == S - 1, gy_seed, cot_in)

            slot_b = jnp.remainder(mb_idx, W)
            res_b = [
                leaf if not dep
                else lax.dynamic_index_in_dim(r, slot_b, 0, keepdims=False)
                for r, leaf, dep in zip(ring, cur_leaves, dep_mask)]
            vjp_b = jax.tree_util.tree_unflatten(res_treedef, res_b)
            gp, gx = vjp_b(g_in)

            bmask = active_b
            g_stage = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(bmask, g, jnp.zeros_like(g)),
                g_stage, gp)
            lmask = bmask & (stage == S - 1)
            g_loss = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(lmask, g, jnp.zeros_like(g)),
                g_loss, g_lp_m)
            loss_acc = loss_acc + jnp.where(
                lmask, l.astype(jnp.float32) / M, 0.0)
            gx_cur = lax.dynamic_index_in_dim(g_x, mb_idx, 0, keepdims=False)
            g_x = lax.dynamic_update_index_in_dim(
                g_x, jnp.where(bmask & (stage == 0), gx, gx_cur),
                mb_idx, 0)

            # ---- hops: activations forward, cotangents backward
            act_next = lax.ppermute(y, axis_name, fwd_perm)
            cot_next = lax.ppermute(gx, axis_name, bwd_perm)
            return (act_next, cot_next, ring, g_stage, g_loss, g_x,
                    loss_acc), None

        ring0 = [jnp.zeros((W,) + s.shape, s.dtype) if dep
                 else jnp.zeros((), jnp.float32)   # placeholder, unused
                 for s, dep in zip(res_structs, dep_mask)]
        carry0 = (jnp.zeros(mb_shape, dtype),
                  jnp.zeros(mb_shape, dtype),
                  ring0,
                  jax.tree_util.tree_map(jnp.zeros_like, stage_params),
                  jax.tree_util.tree_map(jnp.zeros_like, loss_params),
                  jnp.zeros((M,) + mb_shape, dtype),
                  jnp.zeros((), jnp.float32))
        carry0 = jax.tree_util.tree_map(
            lambda a: _vary_over(axis_name, a)[0], carry0)
        (_, _, _, g_stage, g_loss, g_x, loss_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T))
        # loss_acc is nonzero on the last stage only; the psum replicates
        # it, so the returned loss is identical on every stage.
        loss = lax.psum(loss_acc, axis_name)
        return loss, (g_stage, g_loss, g_x)

    return fn


# ---------------------------------------------------------------------------
# Interleaved 1F1B: virtual stages x hand-scheduled backward
# ---------------------------------------------------------------------------

def pipeline_interleaved_1f1b(stage_fn: Callable, per_mb_loss: Callable,
                              axis_name: str, rounds: int) -> Callable:
    """Megatron's interleaved 1F1B: ``R`` virtual stages per device AND the
    hand-scheduled O(in-flight) activation stash — the composition of
    :func:`pipeline_loss_interleaved` (bubble shrinks ~R-fold) and
    :func:`pipeline_1f1b` (stash bounded by the schedule's peak in-flight
    count instead of ``M * R`` residual sets under autodiff).

    TPU shape: the schedule is STATIC DATA — a host-side dependency
    simulation (``schedule_sim.build_interleaved_1f1b``) emits
    per-(device, tick) slot/traffic/buffer tables, verified structurally
    before compile, and the scan body is a dumb table-driven machine: one
    masked F slot, one masked B slot, one forward and one backward
    ``ppermute`` per tick. Activations/cotangents wait in ``(R, S)``
    buffers (round x mb-mod-S — the simulator proves no collision);
    vjp residuals live in a ``n_slots``-ring with param-only leaves
    deduplicated PER ROUND (each round's weights appear once, not once
    per in-flight microbatch).

    Requires ``M % S == 0`` (Megatron's microbatch-group constraint) and
    ``stage_params`` leaves shaped ``(R, ...)`` per device (the
    ``stack_block_params_interleaved`` layout after pp-sharding).

    Same return contract as :func:`pipeline_1f1b`: ``fn(stage_params,
    loss_params, microbatches) -> (loss, (g_stage, g_loss_params,
    g_microbatches))`` with ``loss`` already replicated,
    ``g_loss_params`` nonzero on the last device only, ``g_microbatches``
    on device 0 only, ``g_stage`` stage-local. ``per_mb_loss`` must not
    contain collectives (it runs under ``lax.cond``).
    """
    from horovod_tpu.parallel.schedule_sim import build_interleaved_1f1b

    def fn(stage_params, loss_params, microbatches):
        S = lax.psum(1, axis_name)
        d = lax.axis_index(axis_name)
        R = rounds
        M = microbatches.shape[0]
        mb_shape = microbatches.shape[1:]
        dtype = microbatches.dtype

        # psum of a literal over a shard_map axis is concrete at trace
        # time (the flat 1F1B's perm construction relies on the same).
        S_static = int(S)
        sched = build_interleaved_1f1b(S_static, R, M)
        T, n_slots = sched.T, sched.n_slots

        def rows(tab):   # (S, T) -> (T, S) scanned xs
            return jnp.asarray(tab.T, jnp.int32)

        xs = (rows(sched.f_round), rows(sched.f_mb), rows(sched.f_slot),
              rows(sched.fy_slot),
              rows(sched.b_round), rows(sched.b_mb), rows(sched.b_slot),
              rows(sched.by_slot),
              rows(sched.recv_round), rows(sched.recv_mb),
              rows(sched.brecv_round), rows(sched.brecv_mb))

        fwd_perm = [(i, (i + 1) % S_static) for i in range(S_static)]
        bwd_perm = [(i, (i - 1) % S_static) for i in range(S_static)]

        x_struct = jax.ShapeDtypeStruct(mb_shape, dtype)
        p0 = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        dep_mask = _x_dependent_leaf_mask(stage_fn, p0, x_struct)
        res_structs = jax.eval_shape(
            lambda p, xx: jax.tree_util.tree_leaves(
                jax.vjp(stage_fn, p, xx)[1]),
            p0, x_struct)

        def pick(row):
            return lax.dynamic_index_in_dim(row, d, 0, keepdims=False)

        def tick(carry, xrow):
            (act_buf, cot_buf, ring, round_res, y_buf, g_stage, g_loss,
             g_x, loss_acc) = carry
            (fr, fm, fs, fy, br, bm, bs, by, rr, rm, qr, qm) = \
                [pick(r) for r in xrow]

            # ---- F slot --------------------------------------------------
            active_f = fm >= 0
            fr_c = jnp.clip(fr, 0, R - 1)
            fm_c = jnp.clip(fm, 0, M - 1)
            fs_c = jnp.clip(fs, 0, n_slots - 1)
            p_r = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, fr_c, 0,
                                                   keepdims=False),
                stage_params)
            feed = lax.dynamic_index_in_dim(microbatches, fm_c, 0,
                                            keepdims=False)
            buf_x = act_buf[fr_c, jnp.remainder(fm_c, S)]
            x = jnp.where((d == 0) & (fr_c == 0), feed, buf_x)
            y, vjp_fn = jax.vjp(stage_fn, p_r, x)
            cur_leaves, res_treedef = jax.tree_util.tree_flatten(vjp_fn)
            new_ring, new_round = [], []
            for ringl, roundl, leaf, dep in zip(ring, round_res,
                                                cur_leaves, dep_mask):
                if dep:
                    old = lax.dynamic_index_in_dim(ringl, fs_c, 0,
                                                   keepdims=False)
                    new_ring.append(lax.dynamic_update_index_in_dim(
                        ringl, jnp.where(active_f, leaf, old), fs_c, 0))
                    new_round.append(roundl)
                else:
                    oldr = lax.dynamic_index_in_dim(roundl, fr_c, 0,
                                                    keepdims=False)
                    new_round.append(lax.dynamic_update_index_in_dim(
                        roundl, jnp.where(active_f, leaf, oldr), fr_c, 0))
                    new_ring.append(ringl)
            ring, round_res = new_ring, new_round
            # Loss-head outputs: a compact secondary ring, only the last
            # device's final-round slots are assigned (fy >= 0) — y
            # storage scales with the loss stage's in-flight peak, not
            # n_slots on every device.
            fy_c = jnp.clip(fy, 0, y_buf.shape[0] - 1)
            oldy = lax.dynamic_index_in_dim(y_buf, fy_c, 0, keepdims=False)
            y_buf = lax.dynamic_update_index_in_dim(
                y_buf, jnp.where(fy >= 0, y, oldy), fy_c, 0)

            # ---- B slot --------------------------------------------------
            active_b = bm >= 0
            br_c = jnp.clip(br, 0, R - 1)
            bm_c = jnp.clip(bm, 0, M - 1)
            bs_c = jnp.clip(bs, 0, n_slots - 1)
            res_b = [
                lax.dynamic_index_in_dim(ringl, bs_c, 0, keepdims=False)
                if dep else
                lax.dynamic_index_in_dim(roundl, br_c, 0, keepdims=False)
                for ringl, roundl, dep in zip(ring, round_res, dep_mask)]
            vjp_b = jax.tree_util.tree_unflatten(res_treedef, res_b)
            is_last = (br_c == R - 1) & (d == S - 1)
            is_loss_slot = active_b & is_last
            by_c = jnp.clip(by, 0, y_buf.shape[0] - 1)
            y_loss = lax.dynamic_index_in_dim(y_buf, by_c, 0,
                                              keepdims=False)
            l, g_lp_m, gy_seed = _mb_loss_cond(
                per_mb_loss, loss_params, y_loss, bm_c, M, is_loss_slot)
            g_in = jnp.where(is_last, gy_seed,
                             cot_buf[br_c, jnp.remainder(bm_c, S)])
            gp, gx = vjp_b(g_in)

            g_stage = jax.tree_util.tree_map(
                lambda gs, g: lax.dynamic_update_index_in_dim(
                    gs,
                    lax.dynamic_index_in_dim(gs, br_c, 0, keepdims=False)
                    + jnp.where(active_b, g, jnp.zeros_like(g)),
                    br_c, 0),
                g_stage, gp)
            g_loss = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(is_loss_slot, g,
                                           jnp.zeros_like(g)),
                g_loss, g_lp_m)
            loss_acc = loss_acc + jnp.where(
                is_loss_slot, l / M, 0.0)
            gx_mask = active_b & (br_c == 0) & (d == 0)
            gx_cur = lax.dynamic_index_in_dim(g_x, bm_c, 0, keepdims=False)
            g_x = lax.dynamic_update_index_in_dim(
                g_x, jnp.where(gx_mask, gx_cur + gx, gx_cur), bm_c, 0)

            # ---- hops: consume-before-receive ordering holds because the
            # buffer reads above used the PRE-hop carry.
            act_recv = lax.ppermute(y, axis_name, fwd_perm)
            rr_c = jnp.clip(rr, 0, R - 1)
            rm_c = jnp.clip(rm, 0, M - 1)
            slot_a = (rr_c, jnp.remainder(rm_c, S))
            act_buf = act_buf.at[slot_a].set(
                jnp.where(rm >= 0, act_recv, act_buf[slot_a]))
            cot_recv = lax.ppermute(gx, axis_name, bwd_perm)
            qr_c = jnp.clip(qr, 0, R - 1)
            qm_c = jnp.clip(qm, 0, M - 1)
            slot_c = (qr_c, jnp.remainder(qm_c, S))
            cot_buf = cot_buf.at[slot_c].set(
                jnp.where(qm >= 0, cot_recv, cot_buf[slot_c]))

            return (act_buf, cot_buf, ring, round_res, y_buf, g_stage,
                    g_loss, g_x, loss_acc), None

        ring0 = [jnp.zeros((n_slots,) + st.shape, st.dtype) if dep
                 else jnp.zeros((), jnp.float32)
                 for st, dep in zip(res_structs, dep_mask)]
        round0 = [jnp.zeros((R,) + st.shape, st.dtype) if not dep
                  else jnp.zeros((), jnp.float32)
                  for st, dep in zip(res_structs, dep_mask)]
        carry0 = (jnp.zeros((R, S_static) + mb_shape, dtype),
                  jnp.zeros((R, S_static) + mb_shape, dtype),
                  ring0, round0,
                  jnp.zeros((sched.n_y_slots,) + mb_shape, dtype),
                  jax.tree_util.tree_map(jnp.zeros_like, stage_params),
                  jax.tree_util.tree_map(jnp.zeros_like, loss_params),
                  jnp.zeros((M,) + mb_shape, dtype),
                  jnp.zeros((), jnp.float32))
        carry0 = jax.tree_util.tree_map(
            lambda a: _vary_over(axis_name, a)[0], carry0)
        (_, _, _, _, _, g_stage, g_loss, g_x, loss_acc), _ = lax.scan(
            tick, carry0, xs)
        loss = lax.psum(loss_acc, axis_name)   # replicated, like 1F1B
        return loss, (g_stage, g_loss, g_x)

    return fn
