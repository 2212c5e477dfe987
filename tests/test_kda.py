"""The chunked gated delta rule (``ops/kda.chunk_kda``) against the
recurrence position by position: forward and ``jax.grad`` of every input,
whole chunks and ragged rows, one group and several, decays at
the initialisation's extremes, write strengths at 0 and 1, and bfloat16
inputs against float32. The forward kernel (run by the Pallas interpreter
off a TPU) is also held to the forward in plain XLA that it replaced, and
the backward's private interface to what patches it."""

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


def recurrence(q, k, v, g, beta, scale):
    """``S' = Diag(exp g_t) S``, ``S_t = S' + beta_t k_t (v_t - S'^T
    k_t)^T``, ``o_t = scale S_t^T q_t``, one position at a time, in
    float32."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    dk, dv = q.shape[-1], v.shape[-1]

    def row(q, k, v, g, beta):          # (T, dk) ... one head
        def step(S, x):
            qt, kt, vt, gt, bt = x
            S = jnp.exp(gt)[:, None] * S
            S = S + bt * kt[:, None] * (vt - S.T @ kt)[None, :]
            return S, scale * (S.T @ qt)
        return jax.lax.scan(step, jnp.zeros((dk, dv)), (q, k, v, g, beta))[1]

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
        S1, O = jax.lax.scan(kda._step, S,
                             kda._chunks_first(kda._intra(*grp)))
        return S1, (kda._chunks_back(O), S)

    _, (O, starts) = jax.lax.scan(body, jnp.zeros((B, H, dk, dv)),
                                  (q, k, v, g, beta))
    return O.astype(v.dtype), starts


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
    """The forward is one kernel whose grid steps are chunks of a (row,
    head), its backward scans over chunks and groups, never over
    positions: no scan in the jaxpr of the gradient is as long as the row,
    and the kernel is called once."""
    B, T, H, dk, dv = 1, 2048, 2, 16, 16
    q, k, v, g, b = inputs(4, B, T, H, dk, dv, (1e-3, 1.6))
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(chunk_kda(
        q, k, v, g, b))))(q))
    lengths = [int(n) for n in re.findall(r"length=(\d+)", text)]
    assert lengths and max(lengths) <= GROUP, lengths
    assert text.count("name=kda_fwd") == 1
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
        got = kda._solve(N, rhs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4 * float(
                                       jnp.max(jnp.abs(want))))


def test_shapes_that_do_not_go_together_are_refused():
    q, k, v, g, b = inputs(5, 1, 32, 2, 16, 8, (1e-3, 1.6))
    with pytest.raises(ValueError, match="do not go together"):
        chunk_kda(q, k[:, :16], v, g, b)
    with pytest.raises(ValueError, match="do not go together"):
        chunk_kda(q, k, v, g, b[..., :1])
