"""The chunked gated delta rule (``ops/kda.chunk_kda``) against the
recurrence position by position: forward and ``jax.grad`` of every input,
whole chunks and ragged rows, one group and several, decays at
the initialisation's extremes, write strengths at 0 and 1, and bfloat16
inputs against float32. The two kernels (run by the Pallas interpreter off
a TPU) are also held to the forward and the backward in plain XLA that
they replaced, kept here as their reference, and the backward's private
interface to what patches it."""

import importlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.kda import (CHUNK, GROUP, SUB, chunk_kda, chunks_run,
                                 saved_state_bytes)

kda = importlib.import_module("horovod_tpu.ops.kda")
_F32 = jnp.float32


# ---------------------------------------------------------------------------
# the plain-XLA form the kernels replaced: a chunk's own factors for any
# leading batch of chunks (``_intra``, the module docstring's names), the
# step on the state and its backward, over grouped inputs
# ---------------------------------------------------------------------------

def _mm(spec, *xs):
    """A product in float32 at full precision (on a TPU a float32 product is
    otherwise rounded like bfloat16)."""
    return jnp.einsum(spec, *xs, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=_F32)


def _intra(q, k, v, g, beta):
    """A chunk's own factors, for any leading batch of chunks. ``q``, ``k``
    (..., C, dk), ``v`` (..., C, dv), ``g`` (..., C, dk) float32 log-decays,
    ``beta`` (..., C) float32. Returns float32 ``(Qd (C, dk), Aqk (C, C),
    W (C, dk), U0 (C, dv), Kr (C, dk), gamma (dk,))``, the names of the
    module's docstring: ``Qd = scale Q e^G`` (``scale = dk ** -0.5``),
    ``W`` and ``U0`` the solve's two halves (``U = U0 - W S0``), ``Kr = K
    e^(G_C - G)`` and ``gamma = e^G_C``."""
    C, dk = k.shape[-2:]
    scale = dk ** -0.5
    ns, sub = C // SUB, SUB
    q, k, v = (x.astype(_F32) for x in (q, k, v))
    G = jnp.cumsum(g, axis=-2)
    last = G[..., -1, :]
    Qd = scale * q * jnp.exp(G)
    Kd = k * jnp.exp(G)
    Kr = k * jnp.exp(last[..., None, :] - G)

    # across sub-chunks: rows of sub-chunk I against every earlier
    # position, both factors taken from ``ref_I``, G at the position before
    # sub-chunk I (0 before the chunk's first)
    Gs = G.reshape(*G.shape[:-2], ns, sub, dk)
    ref = jnp.concatenate([jnp.zeros_like(Gs[..., :1, -1, :]),
                           Gs[..., :-1, -1, :]], axis=-2)      # (..., ns, dk)
    rows = jnp.exp(Gs - ref[..., None, :])                     # <= 1
    pos = jnp.arange(C)
    before = pos[None, :] < (jnp.arange(ns) * sub)[:, None]    # (ns, C)
    cols = jnp.exp(jnp.where(before[:, :, None],
                             ref[..., :, None, :] - G[..., None, :, :],
                             -jnp.inf))                        # <= 1, or 0
    kcols = k[..., None, :, :] * cols                   # (..., ns, C, dk)
    qs, ks = (x.reshape(*x.shape[:-2], ns, sub, dk) for x in (q, k))
    # (..., ns, sub, C): sub-chunk I's rows against the positions before it
    off_qk = _mm("...isc,...ijc->...isj", qs * rows, kcols)
    off_kk = _mm("...isc,...ijc->...isj", ks * rows, kcols)

    # inside a sub-chunk: exp(G_i - G_j) element by element, j <= i
    lower = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    inside = jnp.exp(jnp.where(lower[:, :, None],
                               Gs[..., :, None, :] - Gs[..., None, :, :],
                               -jnp.inf))           # (..., ns, s, s, dk)
    diag_qk = jnp.sum(qs[..., :, None, :] * ks[..., None, :, :] * inside, -1)
    diag_kk = jnp.sum(ks[..., :, None, :] * ks[..., None, :, :] * inside, -1)
    diag_kk = jnp.where(jnp.eye(sub, dtype=bool), 0.0, diag_kk)

    def whole(off, diag):
        # (..., ns, sub, C) + the diagonal blocks -> (..., C, C)
        blocks = off.reshape(*off.shape[:-1], ns, sub)
        eye = jnp.eye(ns, dtype=_F32)[:, None, :, None]
        return (blocks + diag[..., :, :, None, :] * eye).reshape(
            *off.shape[:-3], C, C)

    Aqk = scale * whole(off_qk, diag_qk)
    A = whole(off_kk, diag_kk)
    # (I + diag(beta) A) [W | U0] = diag(beta) [Kd | V], A strictly lower
    rhs = beta[..., None] * jnp.concatenate([Kd, v], axis=-1)
    solved = _solve(beta[..., None] * A, rhs)
    return Qd, Aqk, solved[..., :dk], solved[..., dk:], Kr, jnp.exp(last)


def _solve(N, rhs):
    """``(I + N)^-1 rhs`` for ``N`` (..., C, C) strictly lower, by blocks of
    ``SUB``: each diagonal block's inverse as ``(I - D)(I + D^2)(I +
    D^4)(I + D^8)`` (``D^SUB = 0``), then forward substitution over the
    block rows by products. XLA's triangular solve walks the chunk's 64
    rows one at a time."""
    C = N.shape[-1]
    blk = lambda I, J: N[..., I * SUB:(I + 1) * SUB, J * SUB:(J + 1) * SUB]
    diag = jnp.stack([blk(I, I) for I in range(C // SUB)], axis=-3)
    inv, power, m = jnp.eye(SUB, dtype=N.dtype) - diag, diag, 2
    while m < SUB:
        power = _mm("...ij,...jk->...ik", power, power)
        inv = inv + _mm("...ij,...jk->...ik", inv, power)
        m *= 2
    rows = []
    for I in range(C // SUB):
        r = rhs[..., I * SUB:(I + 1) * SUB, :]
        for J in range(I):
            r = r - _mm("...ij,...jv->...iv", blk(I, J), rows[J])
        rows.append(_mm("...ij,...jv->...iv", inv[..., I, :, :], r))
    return jnp.concatenate(rows, axis=-2)


def _step(S, xs):
    """One chunk on the state ``S`` (batch, dk, dv): ``(S1, O)``."""
    Qd, Aqk, W, U0, Kr, gamma = xs
    U = U0 - _mm("...cd,...dv->...cv", W, S)
    O = (_mm("...cd,...dv->...cv", Qd, S)
         + _mm("...ij,...jv->...iv", Aqk, U))
    S1 = gamma[..., :, None] * S + _mm("...cd,...cv->...dv", Kr, U)
    return S1, O


def _step_bwd(dS1, xs):
    """:func:`_step` backward for one chunk: ``dS1`` is the cotangent of
    the state it left, ``xs`` its factors, its ``S0`` and its output's
    cotangent. ``(dS0, d factors)``."""
    (Qd, Aqk, W, U0, Kr, gamma), S0, dO = xs
    U = U0 - _mm("...cd,...dv->...cv", W, S0)
    dU = (_mm("...ij,...iv->...jv", Aqk, dO)
          + _mm("...cd,...dv->...cv", Kr, dS1))
    dQd = _mm("...cv,...dv->...cd", dO, S0)
    dAqk = _mm("...iv,...jv->...ij", dO, U)
    dW = -_mm("...cv,...dv->...cd", dU, S0)
    dKr = _mm("...cv,...dv->...cd", U, dS1)
    dgamma = jnp.sum(S0 * dS1, axis=-1)
    dS0 = (gamma[..., :, None] * dS1
           + _mm("...cd,...cv->...dv", Qd, dO)
           - _mm("...cd,...cv->...dv", W, dU))
    return dS0, (dQd, dAqk, dW, dU, dKr, dgamma)


def _chunks_first(tree):
    """(batch, heads, chunks, ...) -> (chunks, batch, heads, ...)."""
    return jax.tree_util.tree_map(lambda x: jnp.moveaxis(x, 2, 0), tree)


def _chunks_back(tree):
    return jax.tree_util.tree_map(lambda x: jnp.moveaxis(x, 0, 2), tree)


def recurrence(q, k, v, g, beta, scale, dtype=jnp.float32):
    """``S' = Diag(exp g_t) S``, ``S_t = S' + beta_t k_t (v_t - S'^T
    k_t)^T``, ``o_t = scale S_t^T q_t``, one position at a time, in
    ``dtype``."""
    q, k, v, g, beta = (x.astype(dtype) for x in (q, k, v, g, beta))
    dk, dv = q.shape[-1], v.shape[-1]

    def row(q, k, v, g, beta):          # (T, dk) ... one head
        def step(S, x):
            qt, kt, vt, gt, bt = x
            S = jnp.exp(gt)[:, None] * S
            S = S + bt * kt[:, None] * (vt - S.T @ kt)[None, :]
            return S, scale * (S.T @ qt)
        return jax.lax.scan(step, jnp.zeros((dk, dv), dtype),
                            (q, k, v, g, beta))[1]

    heads = jax.vmap(row, in_axes=(1, 1, 1, 1, 1), out_axes=1)
    return jax.vmap(heads)(q, k, v, g, beta)


def inputs(seed, B, T, H, dk, dv, decay, beta=None):
    """Unit-norm q and k a head (KDA takes both through an L2 norm), log
    decays log-uniform in ``decay`` (two ends, negated), ``beta`` uniform in
    [0, 1] or the number given."""
    r = np.random.default_rng(seed)
    q, k = (r.standard_normal((B, T, H, dk)) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.standard_normal((B, T, H, dv))
    g = -np.exp(r.uniform(np.log(decay[0]), np.log(decay[1]),
                          (B, T, H, dk)))
    b = (r.uniform(0, 1, (B, T, H)) if beta is None
         else np.full((B, T, H), float(beta)))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, b)]


# (T, d_k, d_v, decay range, beta)
_CASES = {
    "whole_chunks": (128, 32, 16, (1e-3, 1.6), None),
    "ragged": (100, 32, 16, (1e-3, 1.6), None),
    "shorter_than_a_chunk": (37, 32, 16, (1e-3, 1.6), None),
    # 18 chunks: two whole groups and one padded, dS carried between them
    "several_groups": (1100, 16, 8, (1e-3, 0.1), None),
    "fastest_decay": (128, 32, 16, (1.6, 1.6), None),       # -100 a chunk
    "slowest_decay": (96, 32, 16, (1e-3, 1e-3), None),
    "beta_zero": (64, 32, 16, (1e-3, 1.6), 0.0),
    "beta_one": (64, 32, 16, (1e-3, 1.6), 1.0),
}


@pytest.mark.parametrize("case", list(_CASES), ids=list(_CASES))
def test_chunked_form_is_the_recurrence(case):
    T, dk, dv, decay, beta = _CASES[case]
    B, H = 2, 2
    q, k, v, g, b = inputs(0, B, T, H, dk, dv, decay, beta)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((B, T, H, dv)),
                    jnp.float32)
    scale = dk ** -0.5
    with jax.default_matmul_precision("highest"):
        got = chunk_kda(q, k, v, g, b)
        want = recurrence(q, k, v, g, b, scale)
        assert got.shape == want.shape and got.dtype == v.dtype
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        argnums = (0, 1, 2, 3, 4)
        mine = jax.grad(lambda *a: jnp.sum(chunk_kda(*a) * w), argnums)(
            q, k, v, g, b)
        theirs = jax.grad(lambda *a: jnp.sum(recurrence(*a, scale) * w),
                          argnums)(q, k, v, g, b)
    for name, a, c in zip("q k v g beta".split(), mine, theirs):
        assert bool(jnp.all(jnp.isfinite(a))), name
        top = float(jnp.max(jnp.abs(c)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-3, atol=1e-4 * max(top, 1.0),
                                   err_msg=name)


def xla_forward(q, k, v, g, beta):
    """The forward as plain XLA over grouped inputs: each group's factors
    by ``_intra``, then a scan of ``_step`` over its chunks, inside a scan
    over the groups. ``(O in v's dtype, the state at every group's
    start)``, what ``_forward`` returns."""
    B, H, dk, dv = q.shape[1], q.shape[2], q.shape[-1], v.shape[-1]

    def body(S, grp):
        S1, O = jax.lax.scan(_step, S, _chunks_first(_intra(*grp)))
        return S1, (_chunks_back(O), S)

    _, (O, starts) = jax.lax.scan(body, jnp.zeros((B, H, dk, dv)),
                                  (q, k, v, g, beta))
    return O.astype(v.dtype), starts


def xla_backward(res, dO):
    """The backward as plain XLA, ``_kda_bwd``'s interface: a reverse scan
    over the groups carrying ``dS``; a group's factors again under
    ``jax.vjp`` of ``_intra``, its chunks run forward from the kept start
    for each chunk's ``S0``, walked back by ``_step_bwd``, and the factors'
    cotangents pulled back to ``(dq, dk, dv, dg, dbeta)``."""
    q, k, v, g, beta, starts = res

    def body(dS, xs):
        grp, S, dO_g = xs
        factors, pull = jax.vjp(_intra, *grp)
        factors = _chunks_first(factors)
        _, S0s = jax.lax.scan(lambda S, x: (_step(S, x)[0], S), S, factors)
        dS, d = jax.lax.scan(_step_bwd, dS,
                             (factors, S0s,
                              _chunks_first(dO_g.astype(_F32))),
                             reverse=True)
        return dS, pull(_chunks_back(d))

    B, H, dk, dv = q.shape[1], q.shape[2], q.shape[-1], v.shape[-1]
    _, grads = jax.lax.scan(body, jnp.zeros((B, H, dk, dv), _F32),
                            ((q, k, v, g, beta), starts, dO), reverse=True)
    return grads


def grouped(x, T):
    """(B, T, H, ...) -> (groups, B, H, group, chunk, ...), padded as
    ``chunk_kda`` pads."""
    B, _, H = x.shape[:3]
    chunk, group, groups, padded = kda._layout(T)
    x = jnp.pad(x, [(0, 0), (0, padded - T)] + [(0, 0)] * (x.ndim - 2))
    x = x.reshape(B, groups, group, chunk, H, *x.shape[3:])
    return jnp.moveaxis(x, (1, 4), (0, 2))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_CASES), ids=list(_CASES))
def test_the_forward_kernel_is_the_xla_forward(case, dtype):
    """The kernel against the forward it replaced, on the same grouped
    inputs (q, k, v in ``dtype``): the output in v's dtype, and the state
    at every group's start, which the backward reads, to float32's
    rounding; and the output against the recurrence position by
    position."""
    T, dk, dv, decay, beta = _CASES[case]
    q, k, v, g, b = inputs(7, 2, T, 2, dk, dv, decay, beta)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    xs = [grouped(x, T) for x in (q, k, v, g, b)]
    with jax.default_matmul_precision("highest"):
        got, got_starts = jax.jit(kda._forward)(*xs)
        want, want_starts = jax.jit(xla_forward)(*xs)
        exact = recurrence(q, k, v, g, b, dk ** -0.5)
    assert got.dtype == want.dtype == v.dtype and got.shape == want.shape
    assert got_starts.shape == want_starts.shape == (
        xs[0].shape[0], 2, 2, dk, dv)
    top = max(float(jnp.max(jnp.abs(want.astype(jnp.float32)))), 1e-6)
    ulp = 1e-5 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=ulp,
                               atol=ulp * top)
    top = max(float(jnp.max(jnp.abs(want_starts))), 1e-6)
    np.testing.assert_allclose(np.asarray(got_starts),
                               np.asarray(want_starts), rtol=1e-5,
                               atol=1e-5 * top)
    o = jnp.moveaxis(got, (0, 2), (1, 4)).reshape(2, -1, 2, dv)[:, :T]
    rel = float(jnp.linalg.norm(o.astype(jnp.float32) - exact)
                / max(float(jnp.linalg.norm(exact)), 1e-30))
    assert rel < (1e-5 if dtype == "float32" else 1e-2), rel


@pytest.mark.parametrize("beta", [0.5, 1.0])
def test_the_forward_kernel_holds_the_hardest_chunk(beta):
    """One unit key repeated with no decay: ``A`` is all ones under the
    diagonal, where the powers in the inverse of the chunk's triangular
    system cancel (at ``beta`` 1 the chunked form itself parts from the
    recurrence by ~4e-5 in float32). The kernel's output and kept states,
    two groups of whole chunks, lie as near the recurrence as the forward
    it replaced."""
    B, T, H, dk, dv = 1, 1024, 2, 32, 16
    q, _, v, _, _ = inputs(10, B, T, H, dk, dv, (1e-3, 1.6))
    key = np.random.default_rng(11).standard_normal(dk)
    k = jnp.broadcast_to(jnp.asarray(key / np.linalg.norm(key), jnp.float32),
                         q.shape)
    g = jnp.zeros_like(q)
    b = jnp.full((B, T, H), beta, jnp.float32)
    xs = [grouped(x, T) for x in (q, k, v, g, b)]
    with jax.default_matmul_precision("highest"):
        got, got_starts = jax.jit(kda._forward)(*xs)
        want, want_starts = jax.jit(xla_forward)(*xs)
        exact = recurrence(q, k, v, g, b, dk ** -0.5)
    rel = lambda a, c: float(jnp.linalg.norm(a - c) / jnp.linalg.norm(c))
    rowed = lambda o: jnp.moveaxis(o, (0, 2), (1, 4)).reshape(B, T, H, dv)
    mine, theirs = rel(rowed(got), exact), rel(rowed(want), exact)
    assert mine < max(2 * theirs, 1e-5) and mine < 1e-4, (mine, theirs)
    # the kept states carry the same conditioning: within a few times the
    # outputs' distance of each other
    assert rel(got_starts, want_starts) < 5e-4


def _cotangent(seed, T, dv, dtype):
    """An output cotangent (1, T, 2, dv) in the grouped layout."""
    r = np.random.default_rng(seed)
    return grouped(jnp.asarray(r.standard_normal((2, T, 2, dv)), dtype), T)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_CASES), ids=list(_CASES))
def test_the_backward_kernel_is_the_xla_backward(case, dtype):
    """The kernel against the backward it replaced, on the same residuals
    (the inputs in ``dtype`` and the forward kernel's kept states) and
    output cotangent: the five gradients in the inputs' dtypes and grouped
    layout, to float32's rounding (a bfloat16 gradient to its own)."""
    T, dk, dv, decay, beta = _CASES[case]
    q, k, v, g, b = inputs(12, 2, T, 2, dk, dv, decay, beta)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    xs = [grouped(x, T) for x in (q, k, v, g, b)]
    dO = _cotangent(13, T, dv, dtype)
    with jax.default_matmul_precision("highest"):
        res = (*xs, jax.jit(kda._forward)(*xs)[1])
        got = jax.jit(kda._kda_bwd)(res, dO)
        want = jax.jit(xla_backward)(res, dO)
    for name, a, c, x in zip("q k v g beta".split(), got, want, xs):
        assert a.dtype == c.dtype == x.dtype, name
        assert a.shape == c.shape == x.shape, name
        a, c = (np.asarray(y, np.float32) for y in (a, c))
        assert np.all(np.isfinite(a)), name
        top = max(float(np.max(np.abs(c))), 1e-6)
        ulp = 1e-5 if x.dtype == jnp.float32 else 1e-2
        np.testing.assert_allclose(a, c, rtol=ulp, atol=ulp * top,
                                   err_msg=name)


@pytest.mark.parametrize("beta", [0.5, 1.0])
def test_the_backward_kernel_holds_the_hardest_chunk(beta):
    """The forward's hardest chunk (one unit key repeated with no decay,
    two groups of whole chunks) backward: the kernel's five gradients lie
    as near ``jax.vjp`` of the float32 recurrence as the backward it
    replaced, whose transposed solve meets the same cancelling powers."""
    B, T, H, dk, dv = 1, 1024, 2, 32, 16
    q, _, v, _, _ = inputs(14, B, T, H, dk, dv, (1e-3, 1.6))
    key = np.random.default_rng(15).standard_normal(dk)
    k = jnp.broadcast_to(jnp.asarray(key / np.linalg.norm(key), jnp.float32),
                         q.shape)
    g = jnp.zeros_like(q)
    b = jnp.full((B, T, H), beta, jnp.float32)
    w = jnp.asarray(np.random.default_rng(16).standard_normal(
        (B, T, H, dv)), jnp.float32)
    xs = [grouped(x, T) for x in (q, k, v, g, b)]
    with jax.default_matmul_precision("highest"):
        res = (*xs, jax.jit(kda._forward)(*xs)[1])
        got = jax.jit(kda._kda_bwd)(res, grouped(w, T))
        want = jax.jit(xla_backward)(res, grouped(w, T))
        _, pull = jax.vjp(lambda *a: recurrence(*a, dk ** -0.5),
                          q, k, v, g, b)
        exact = [grouped(x, T) for x in pull(w)]
    rel = lambda a, c: float(jnp.linalg.norm(a - c) / jnp.linalg.norm(c))
    for name, a, c, e in zip("q k v g beta".split(), got, want, exact):
        mine, theirs = rel(a, e), rel(c, e)
        assert mine < max(2 * theirs, 1e-5) and mine < 1e-3, (
            name, mine, theirs)


def test_the_backward_kernel_lies_nearer_float64_than_the_xla_backward():
    """Rows of one chunk (as the model tests run them), against ``jax.vjp``
    of the recurrence in float64: each of the kernel's five gradients lies
    no further than the XLA backward's, and so does what a head's ``A_log``
    reads of ``dg``, its sum against the decays, which cancels. The kernel
    takes a pair's part of ``dg`` only at the rows between its two
    positions, and the decays between two rows of a sub-chunk from their
    own sums; XLA's backward subtracts suffix sums of the whole chunk and
    cumulative sums of the whole row, losing the digits that cancel."""
    T, dk, dv = 40, 16, 16
    ungroup = lambda x: jnp.moveaxis(x, (0, 2), (1, 4)).reshape(
        1, -1, 2, *x.shape[5:])[:, :T]
    worst = {"kernel": np.zeros(6), "xla": np.zeros(6)}
    for seed in range(6):
        q, k, v, g, b = inputs(20 + seed, 1, T, 2, dk, dv, (1e-3, 1.6))
        w = jnp.asarray(np.random.default_rng(30 + seed).standard_normal(
            (1, T, 2, dv)), jnp.float32)
        with jax.enable_x64(True):
            _, pull = jax.vjp(lambda *a: recurrence(
                *a, dk ** -0.5, dtype=jnp.float64),
                *(jnp.asarray(x, jnp.float64) for x in (q, k, v, g, b)))
            exact = [np.asarray(x) for x in pull(jnp.asarray(w, jnp.float64))]
        xs = [grouped(x, T) for x in (q, k, v, g, b)]
        with jax.default_matmul_precision("highest"):
            res = (*xs, jax.jit(kda._forward)(*xs)[1])
            for name, backward in (("kernel", kda._kda_bwd),
                                   ("xla", xla_backward)):
                got = [np.asarray(ungroup(x), np.float64) for x in
                       jax.jit(backward)(res, grouped(w, T))]
                apart = [np.linalg.norm(a - e) / np.linalg.norm(e)
                         for a, e in zip(got, exact)]
                reads = lambda dg: np.sum(dg * np.asarray(g), axis=(1, 3))
                apart.append(np.max(np.abs(reads(got[3]) - reads(exact[3]))
                                    / np.abs(reads(exact[3]))))
                worst[name] = np.maximum(worst[name], apart)
    assert np.all(worst["kernel"] <= worst["xla"]), worst


@pytest.mark.parametrize("width", [SUB, CHUNK])
def test_the_kernels_sums_run_down_and_up_the_rows(width):
    """``_sums_down`` (the forward's cumulative log-decays), ``_sums_up``
    and ``_sums_before`` (the backward's: what reads ``g_m`` in the rows
    from ``m`` on, or before it) against NumPy's cumulative sums, over
    runs of ``width`` rows of a chunk."""
    x = np.random.default_rng(17).standard_normal((2, CHUNK, 8))
    pos = np.broadcast_to(np.arange(CHUNK)[None, :, None] % width, x.shape)
    runs = x.reshape(2, CHUNK // width, width, 8)
    down = np.cumsum(runs, 2).reshape(x.shape)
    up = np.cumsum(runs[:, :, ::-1], 2)[:, :, ::-1].reshape(x.shape)
    for fn, want in ((kda._sums_down, down), (kda._sums_up, up),
                     (kda._sums_before, down - x)):
        got = jax.jit(fn, static_argnums=2)(jnp.asarray(x, jnp.float32),
                                            jnp.asarray(pos), width)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)


def test_a_backward_patched_in_as_the_controls_do_is_the_one_run():
    """``benchmark/controls_kimi_linear.py`` puts a faulty backward into
    ``chunk_kda`` by patching ``_kda`` with a ``custom_vjp`` of ``_kda_fwd``
    and ``_kda_bwd`` run on each group alone (each group's reverse scan
    from ``dS = 0``). Through that interface the output is the sound one
    and the gradient of every input that reads dS moves: a ``chunk_kda``
    that stopped calling the module's ``_kda`` would read as the sound
    system."""
    T, dk, dv, decay, _ = _CASES["several_groups"]
    q, k, v, g, b = inputs(8, 1, T, 2, dk, dv, decay)
    w = jnp.asarray(np.random.default_rng(9).standard_normal((1, T, 2, dv)),
                    jnp.float32)

    @jax.custom_vjp
    def faulty(*xs):
        return kda._kda(*xs)

    def bwd(res, dO):
        one = lambda xs: kda._kda_bwd(tuple(x[None] for x in xs[:-1]),
                                      xs[-1][None])
        return tuple(x[:, 0] for x in jax.lax.map(one, (*res, dO)))

    faulty.defvjp(kda._kda_fwd, bwd)
    with jax.default_matmul_precision("highest"):
        # as the check pulls a cotangent back: through jax.vjp, which runs
        # the patched rule's forward and never its primal
        want, pull = jax.vjp(chunk_kda, q, k, v, g, b)
        sound = pull(w)
        with mock.patch.object(kda, "_kda", faulty):
            out, pull = jax.vjp(chunk_kda, q, k, v, g, b)
            dropped = pull(w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-7)
    apart = {name: float(jnp.linalg.norm(a - c) / jnp.linalg.norm(c))
             for name, a, c in zip("q k v g beta".split(), dropped, sound)}
    assert apart["q"] < 1e-5, apart          # dq reads no dS
    for name in ("k", "v", "g", "beta"):
        assert apart[name] > 1e-2, apart


def test_bf16_inputs_stay_near_float32():
    """q, k, v in bfloat16 (the compute dtype), the state and decays in
    float32: the output is v's dtype and within bfloat16's rounding of the
    float32 recurrence on the same (rounded) inputs, forward and
    backward."""
    B, T, H, dk, dv = 1, 96, 2, 32, 32
    q, k, v, g, b = inputs(3, B, T, H, dk, dv, (1e-3, 1.6))
    q16, k16, v16 = (x.astype(jnp.bfloat16) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        got = chunk_kda(q16, k16, v16, g, b)
        want = recurrence(q16, k16, v16, g, b, dk ** -0.5)
        assert got.dtype == jnp.bfloat16
        rel = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                    / jnp.linalg.norm(want))
        assert rel < 1e-2, rel
        dq = jax.grad(lambda q: jnp.sum(chunk_kda(
            q, k16, v16, g, b).astype(jnp.float32)))(q16)
        dq_want = jax.grad(lambda q: jnp.sum(recurrence(
            q, k16, v16, g, b, dk ** -0.5)))(q16.astype(jnp.float32))
    assert dq.dtype == jnp.bfloat16
    rel = float(jnp.linalg.norm(dq.astype(jnp.float32) - dq_want)
                / jnp.linalg.norm(dq_want))
    assert rel < 2e-2, rel


def test_no_position_is_visited_one_at_a_time():
    """The forward and the backward are one kernel each, whose grid steps
    are chunks of a (row, block of heads): the gradient's jaxpr calls each
    once, no scan in it is longer than a group (the backward has none
    left), and the grids' (row, head, chunk) steps are the row's chunks,
    once a head forward and once backward."""
    B, T, H, dk, dv = 1, 2048, 2, 16, 16
    q, k, v, g, b = inputs(4, B, T, H, dk, dv, (1e-3, 1.6))
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(chunk_kda(
        q, k, v, g, b))))(q))
    lengths = [int(n) for n in re.findall(r"length=(\d+)", text)]
    assert max(lengths, default=0) <= GROUP, lengths
    assert text.count("name=kda_fwd") == 1
    assert text.count("name=kda_bwd") == 1
    heads, grid = kda._grid(grouped(q, T))
    assert heads * np.prod(grid) == B * H * chunks_run(T)


def test_what_the_forward_keeps_is_counted_from_shapes():
    """One float32 state a head at the start of every group; chunks of the
    row padded to whole groups."""
    assert chunks_run(16384) == 256
    assert saved_state_bytes(1, 16384, 32, 128, 128) == 32 * 32 * 128 * \
        128 * 4
    assert chunks_run(100) == 2 and chunks_run(37) == 1
    assert chunks_run(1100) == 24        # 18 chunks in 3 groups of 8
    assert (CHUNK, GROUP, SUB) == (64, 8, 16)


@pytest.mark.parametrize("beta", [0.5, 1.0])
def test_the_chunks_triangular_system_is_solved_by_blocks(beta):
    """``(I + N)^-1 rhs`` by blocks against XLA's triangular solve, for
    ``N = diag(beta) A`` of unit keys, and for the hardest case a chunk can
    hold: one key repeated with no decay, where ``A`` is all ones under the
    diagonal and the inverse's powers cancel."""
    import importlib
    kda = importlib.import_module("horovod_tpu.ops.kda")
    r = np.random.default_rng(6)
    k = r.standard_normal((3, 64, 32))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    cases = [np.tril(k @ k.transpose(0, 2, 1), -1),
             np.tril(np.ones((1, 64, 64)), -1)]
    for A in cases:
        N = jnp.asarray(beta * A, jnp.float32)
        rhs = jnp.asarray(r.standard_normal((len(A), 64, 40)), jnp.float32)
        want = jax.lax.linalg.triangular_solve(
            N, rhs, left_side=True, lower=True, unit_diagonal=True)
        got = _solve(N, rhs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4 * float(
                                       jnp.max(jnp.abs(want))))


def test_shapes_that_do_not_go_together_are_refused():
    q, k, v, g, b = inputs(5, 1, 32, 2, 16, 8, (1e-3, 1.6))
    with pytest.raises(ValueError, match="do not go together"):
        chunk_kda(q, k[:, :16], v, g, b)
    with pytest.raises(ValueError, match="do not go together"):
        chunk_kda(q, k, v, g, b[..., :1])
