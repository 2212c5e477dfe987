"""The chunked gated delta rule with a per-channel decay (Kimi Delta
Attention, KDA): the first layer here whose state is a recurrence.

One head, ``d_k``-wide keys and queries, ``d_v``-wide values, a state ``S``
of ``d_k x d_v`` that starts at zero. At each position ``t``, with ``g_t`` a
log-decay a key channel (``<= 0``) and ``beta_t`` in ``[0, 1]``::

    S'  = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = scale * S_t^T q_t,    scale = d_k ** -0.5

The recurrence is run a chunk of ``C`` positions at a time (the WY / UT
form). Inside a chunk, with ``G`` the log-decays summed from its start
(inclusive), ``S0`` the state at its start and ``u_j`` the delta that
position ``j`` writes::

    A[j, m] = sum_c k_j[c] k_m[c] exp(G_j[c] - G_m[c])   (m < j)
    U  = T diag(beta) (V - (K * e^G) S0),  T = (I + diag(beta) A)^-1
    O  = scale (Q * e^G) S0 + Aqk U
    Aqk[i, j] = scale sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])   (j <= i)
    S1 = Diag(e^G_C) S0 + (K * e^(G_C - G))^T U

Everything that does not read ``S0`` is the chunk's own factors
(:func:`_chunk_factors`); the rest is the step on the state. A position's
log-decay may reach -1.6 and a chunk's sum -100, so ``exp(-G)`` alone would
overflow float32: the factor ``exp(G_i - G_j)`` is only ever formed where
``j <= i``, inside sub-chunks of :data:`SUB` positions element by element,
and across sub-chunks split at the last position before the later one,
where both halves are ``<= 1``. The chunk's triangular system is solved by
blocks of :data:`SUB`.

Both directions are one Pallas kernel each, every product batched over a
block of :data:`HEADS` heads. The forward, ``kda_fwd`` (:func:`_forward`),
walks the chunks of each row in order, a grid step makes one chunk's
factors and runs the step on the states, which stay in VMEM scratch from the
row's first chunk to its last. The chunks go in groups (:data:`GROUP`
chunks of :data:`CHUNK` positions) and the forward keeps the state at the
start of every group (``saved_state_bytes`` counts it). The backward,
``kda_bwd`` (:func:`_backward`), walks the groups in reverse: it runs a
group's chunks forward from the kept state to get each chunk's ``S0``, then
walks them back (:func:`_chunk_bwd`: the factors again, the step's backward,
the factors' own) with ``dS`` in VMEM scratch from the row's last chunk to
its first. No position is visited one at a time.

The state and all decay arithmetic are float32, every product at full
precision (:func:`_dot`); ``q``, ``k``, ``v`` come in the compute dtype and
the output and their gradients leave in theirs. Off a TPU the kernels run in
the Pallas interpreter, as the flash kernels do.
"""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import tracing as _tracing

# the module, not the function ``ops`` exports under its name
_flash = importlib.import_module("horovod_tpu.ops.flash_attention")

__all__ = ["chunk_kda", "chunks_run", "saved_state_bytes", "CHUNK", "GROUP",
           "SUB"]

CHUNK = 64      # positions a chunk
GROUP = 8       # chunks a group: what the forward keeps a state for
SUB = 16        # positions a sub-chunk, inside which exp(G_i - G_j) is whole
HEADS = 8       # heads a grid step of the kernels (as many as divide H)

_F32 = jnp.float32


# ---------------------------------------------------------------------------
# the forward kernel, and what the backward shares with it
# ---------------------------------------------------------------------------

def _dot(a, b, contract=((2,), (1,))):
    """Products of matching heads inside the kernel (``a``, ``b`` (heads,
    ., .)), float32 at full precision (Mosaic's ``contract_precision<fp32>``);
    ``contract`` the dimensions summed over: ``((2,), (2,))`` is ``a @ b.T``
    a head, ``((1,), (1,))`` ``a.T @ b``. One batched product is as many
    independent ones, which the chip runs back to back: a chain of them,
    as the triangular solve is, waits on each product's latency once for
    all the heads."""
    return jax.lax.dot_general(a, b, (contract, ((0,), (0,))),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _sums_down(x, pos, width):
    """Inclusive sums down the rows of ``x`` (heads, rows, .) over runs of
    ``width`` rows, ``pos`` each element's row within its run: a log-step
    prefix sum of rotations (a rotation by ``s`` rows brings row ``i - s``
    to row ``i``)."""
    s = 1
    while s < width:
        x = x + jnp.where(pos >= s, pltpu.roll(x, s, 1), 0.0)
        s *= 2
    return x


def _chunk_factors(q, k, v, g, beta):
    """A chunk's own factors (the module docstring's names) for several
    heads inside a kernel: ``q``, ``k`` (heads, C, dk), ``v`` (heads, C,
    dv), ``g`` (heads, C, dk), ``beta`` (heads, C, 1), all float32. The
    cumulative log-decays ``G``, sub-chunks of :data:`SUB`, ``exp(G_i -
    G_j)`` formed only where ``j <= i``, the system solved by blocks of
    :data:`SUB`, laid out for Mosaic: the pairs inside a
    sub-chunk are taken a diagonal ``i - j`` at a time (the row ``i - j``
    positions up, a rotation) and no array is sliced along lanes. Returns
    ``(Qd, Aqk, W, U0, Kr, gamma)``, ``gamma`` a column ``(heads, dk,
    1)``, and what :func:`_chunk_bwd` reads of the steps: ``(ref, L, A,
    inv, X)``, ``X = [W | U0]`` and ``inv`` the diagonal blocks' inverses
    (zero elsewhere)."""
    h, C, dk = k.shape
    scale = dk ** -0.5
    rows = jax.lax.broadcasted_iota(jnp.int32, (h, C, dk), 1)
    in_sub = rows & (SUB - 1)                   # a row's place in its sub-chunk
    # the cumulative log-decays G = ref + L: L from the start of a row's
    # sub-chunk, ref the sums of the sub-chunks before it (each sub-chunk's
    # last L moved one down, then summed down the chunk). The factors read
    # G only as differences, and L_i - L_j of two rows of one sub-chunk
    # keeps the digits that G_i - G_j would lose to G's size
    L = _sums_down(g, in_sub, SUB)
    ends = jnp.where(in_sub == SUB - 1, L, 0.0)
    ref = _sums_down(jnp.where(rows >= 1, pltpu.roll(ends, 1, 1), 0.0),
                     rows, C)
    G = ref + L
    last = G[:, C - 1:C]                                        # (h, 1, dk)
    Qd = scale * q * jnp.exp(G)
    Kd = k * jnp.exp(G)
    Kr = k * jnp.exp(last - G)
    rowed = jnp.exp(L)                                          # <= 1

    # across sub-chunks: sub-chunk I's rows against the positions before it
    # (h, C, C); sub-chunk 0 has none
    zeros = jnp.zeros((h, SUB, C), _F32)
    off_qk, off_kk = [zeros], [zeros]
    for I in range(1, C // SUB):
        here = slice(I * SUB, (I + 1) * SUB)
        cols = k * jnp.exp(jnp.where(
            rows < I * SUB, (ref[:, I * SUB:I * SUB + 1] - ref) - L,
            -jnp.inf))                                          # <= 1, or 0
        off = _dot(jnp.concatenate([(q * rowed)[:, here],
                                    (k * rowed)[:, here]], axis=1),
                   cols, ((2,), (2,)))
        off_qk.append(off[:, :SUB])
        off_kk.append(off[:, SUB:])
    Aqk, A = (jnp.concatenate(off_qk, axis=1),
              jnp.concatenate(off_kk, axis=1))

    # inside a sub-chunk: the pairs (i, i - d), d = 0 .. SUB - 1
    pair_r = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    pair_c = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    for d in range(SUB):
        up_L, up_k = ((L, k) if d == 0 else
                      (pltpu.roll(L, d, 1), pltpu.roll(k, d, 1)))
        ke = up_k * jnp.exp(jnp.where(in_sub >= d, L - up_L, -jnp.inf))
        at = pair_c == pair_r - d
        Aqk = Aqk + jnp.where(at, jnp.sum(q * ke, 2, keepdims=True), 0.0)
        if d:
            A = A + jnp.where(at, jnp.sum(k * ke, 2, keepdims=True), 0.0)
    Aqk = scale * Aqk

    # (I + diag(beta) A) [W | U0] = diag(beta) [Kd | V], by blocks: each
    # diagonal block's inverse as (I - D)(I + D^2)(I + D^4)(I + D^8), D^SUB
    # = 0, all at once (zero elsewhere), then the block rows in order (XLA's
    # triangular solve walked the chunk's 64 rows one at a time)
    N = beta * A
    rhs = beta * jnp.concatenate([Kd, v], axis=2)
    w = rhs.shape[2]
    D = jnp.where((pair_r // SUB) == (pair_c // SUB), N, 0.0)
    inv = jnp.where(pair_r == pair_c, 1.0, 0.0) - D
    power, m = D, 2
    while m < SUB:
        power = _dot(power, power)
        inv = inv + _dot(inv, power)
        m *= 2
    solved = []
    for I in range(C // SUB):
        here = slice(I * SUB, (I + 1) * SUB)
        r = rhs[:, here]
        if I:
            below = jnp.zeros((h, C - I * SUB, w), _F32)
            r = r - _dot(N[:, here], jnp.concatenate(solved + [below], 1))
        spread = [jnp.zeros((h, I * SUB, w), _F32)] if I else []
        spread.append(r)
        if (I + 1) * SUB < C:
            spread.append(jnp.zeros((h, C - (I + 1) * SUB, w), _F32))
        solved.append(_dot(inv[:, here], jnp.concatenate(spread, 1)))
    solved = jnp.concatenate(solved, axis=1)
    # gamma = exp(G_C) as a column, for the state's rows
    last_col = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (h, dk, C), 2) == C - 1,
        jnp.swapaxes(G, 1, 2), 0.0), 2, keepdims=True)
    return ((Qd, Aqk, solved[:, :, :dk], solved[:, :, dk:], Kr,
             jnp.exp(last_col)), (ref, L, A, inv, solved))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, starts_ref,
                s_ref):
    """One chunk of a block of heads of one row: the heads' factors, then
    the step on their states in ``s_ref``, which stay in VMEM from the
    row's first chunk to its last; at a group's first chunk the states are
    also its ``starts``. Every operation takes the block's heads at once."""
    group, chunk = pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(group == 0, chunk == 0))
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(chunk == 0)
    def _():
        starts_ref[...] = s_ref[...]

    (Qd, Aqk, W, U0, Kr, gamma), _ = _chunk_factors(
        q_ref[...].astype(_F32), k_ref[...].astype(_F32),
        v_ref[...].astype(_F32), g_ref[...], beta_ref[...])
    C, S = Qd.shape[1], s_ref[...]
    both = _dot(jnp.concatenate([W, Qd], axis=1), S)   # W S and Qd S at once
    U = U0 - both[:, :C]
    o_ref[...] = (both[:, C:] + _dot(Aqk, U)).astype(o_ref.dtype)
    s_ref[...] = gamma * S + _dot(Kr, U, ((1,), (1,)))


def _temps(C: int, dk: int, dv: int) -> int:
    """Bytes of the float32 temporaries of one chunk's factors a head: a
    dozen ``(C, d)`` and ``(C, C)`` arrays and the system's right-hand
    side."""
    tile = _flash._tile_bytes
    return (12 * tile(C, dk, 4) + 8 * tile(C, C, 4)
            + 3 * tile(C, dk + dv, 4) + 2 * tile(2 * C, dv, 4))


def _vmem_need(heads: int, C: int, dk: int, dv: int, itemsize: int) -> int:
    """Bytes of VMEM a call of :func:`_forward` takes, margin included, as
    ``ops/flash_attention._vmem_need`` counts: every block in and out twice
    (q, k, v in the compute dtype, g and beta in float32 | O, a group's
    starts), the states in scratch once, and a head's :func:`_temps`."""
    tile = _flash._tile_bytes
    blocks = (2 * tile(C, dk, itemsize) + 2 * tile(C, dv, itemsize)
              + tile(C, dk, 4) + tile(C, 1, 4) + tile(dk, dv, 4))
    return int(_flash._VMEM_MARGIN * heads * (2 * blocks + tile(dk, dv, 4)
                                              + _temps(C, dk, dv)))


def _grid(q):
    """``(heads a grid step, grid)`` of the forward kernel over grouped
    inputs ``(groups, B, H, group, C, dk)``: every row and block of heads,
    and its chunks in order, a group and the chunks in it (the backward's
    grid walks the same blocks, its groups in reverse)."""
    groups, B, H, group = q.shape[:4]
    heads = math.gcd(H, HEADS)
    return heads, (B, H // heads, groups, group)


def _forward(q, k, v, g, beta):
    """The forward over grouped inputs (the layout :func:`chunk_kda` makes,
    ``(groups, B, H, group, C, ...)``) as one kernel: ``(O in v's dtype,
    the float32 state at every group's start (groups, B, H, dk, dv))``."""
    C, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    groups, B, H = q.shape[:3]
    heads, grid = _grid(q)
    block = lambda d: pl.BlockSpec((None, None, heads, None, C, d),
                                   lambda b, h, i, c: (i, b, h, c, 0, 0))
    need = _vmem_need(heads, C, dk, dv, jnp.dtype(q.dtype).itemsize)
    call = pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[block(dk), block(dk), block(dv), block(dk), block(1)],
        out_specs=[block(dv),
                   pl.BlockSpec((None, None, heads, dk, dv),
                                lambda b, h, i, c: (i, b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((groups, B, H, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        interpret=_flash._use_interpret(),
        name="kda_fwd",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=need if need > _flash._VMEM_DEFAULT else None),
    )
    return call(q, k, v, g, beta[..., None])


# ---------------------------------------------------------------------------
# the backward kernel
# ---------------------------------------------------------------------------

def _sums_up(x, pos, width):
    """Inclusive sums up the rows of ``x`` (heads, rows, .) over runs of
    ``width`` rows, ``pos`` each element's row within its run: row ``i``
    holds the sum of rows ``i`` to the run's last (a rotation by ``rows -
    s`` brings row ``i + s`` to row ``i``)."""
    s, n = 1, x.shape[1]
    while s < width:
        x = x + jnp.where(pos + s < width, pltpu.roll(x, n - s, 1), 0.0)
        s *= 2
    return x


def _sums_before(x, pos, width):
    """As :func:`_sums_down`, but row ``i`` holds the sum of the rows
    before it in its run (0 in the run's first)."""
    return _sums_down(jnp.where(pos >= 1, pltpu.roll(x, 1, 1), 0.0), pos,
                      width)


def _chunk_bwd(q, k, v, beta, Aqk, gamma, parts, S0, dS1, dO):
    """One chunk of several heads backward inside the kernel: ``q``, ``k``
    (heads, C, dk), ``v`` (heads, C, dv), ``beta`` (heads, C, 1), what
    :func:`_chunk_factors` made of them that costs products (``Aqk``,
    ``gamma`` and the ``parts``; ``Qd``, ``Kd``, ``Kr`` and ``W``, ``U0``
    are made again here, element by element), ``S0`` the state at the chunk's
    start and ``dS1`` the cotangent of the state it leaves (heads, dk,
    dv), ``dO`` (heads, C, dv), all float32. The step's backward (the
    cotangents of the factors and ``dS0``), then the factors' own:

    * the solve ``X = (I + N)^-1 R``, ``N = diag(beta) A``, ``R =
      diag(beta) [Kd | V]``: ``dR = (I + N)^-T dX`` by the block rows in
      reverse with the diagonal blocks' inverses transposed, ``dN = -dR
      X^T`` (only its strictly lower part is read);
    * a pair product ``P[i, j] = sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])``
      (``Aqk``: queries with keys, ``A``: keys with keys) by the
      forward's split, so ``exp(G_i - G_j)`` is again formed only where
      ``j <= i``: ``dx_i = sum_j dP[i, j] k_j e^(G_i - G_j)``, ``dk_j =
      sum_i dP[i, j] x_i e^(G_i - G_j)``; ``P[i, j]`` reads ``g_m`` for
      ``j < m <= i``, so its part of ``dg`` goes to those rows alone;
    * the element-wise factors, each read of ``g_m`` where its decay spans
      ``m``.

    Returns float32 ``(dq, dk, dv, dg, dbeta, dS0)``, ``dbeta`` a column
    ``(heads, C, 1)``."""
    h, C, dk = k.shape
    scale = dk ** -0.5
    ns = C // SUB
    ref, L, A, inv, X = parts
    G = ref + L
    eG, eR = jnp.exp(G), jnp.exp(G[:, C - 1:C] - G)
    Qd, Kd, Kr = scale * q * eG, k * eG, k * eR
    W, U0 = X[:, :, :dk], X[:, :, dk:]

    # the step: U = U0 - W S0, O = Qd S0 + Aqk U, S1 = gamma S0 + Kr^T U
    U = U0 - _dot(W, S0)
    dU = _dot(Aqk, dO, ((1,), (1,))) + _dot(Kr, dS1)
    both = _dot(jnp.concatenate([dO, dU], axis=1), S0, ((2,), (2,)))
    dQd, dW = both[:, :C], -both[:, C:]
    dKr = _dot(U, dS1, ((2,), (2,)))
    dS0 = gamma * dS1 + _dot(jnp.concatenate([Qd, W], axis=1),
                             jnp.concatenate([dO, -dU], axis=1),
                             ((1,), (1,)))
    dlast_col = jnp.sum(S0 * dS1, 2, keepdims=True) * gamma  # (h, dk, 1)
    dPqk = scale * _dot(dO, U, ((2,), (2,)))

    # the solve, its block rows in reverse: Y_I = inv_I^T (dX_I - sum_{J >
    # I} N_JI^T Y_J), the rows of later blocks spread over zeros as the
    # forward spreads the earlier ones
    N = beta * A
    dX = jnp.concatenate([dW, dU], axis=2)                 # dU0 = dU
    w = dX.shape[2]
    NT, invT = jnp.swapaxes(N, 1, 2), jnp.swapaxes(inv, 1, 2)
    dR = []
    for I in reversed(range(ns)):
        here = slice(I * SUB, (I + 1) * SUB)
        y = dX[:, here]
        if I < ns - 1:
            above = jnp.zeros((h, (I + 1) * SUB, w), _F32)
            y = y - _dot(NT[:, here], jnp.concatenate([above] + dR, 1))
        spread = [jnp.zeros((h, I * SUB, w), _F32)] if I else []
        spread.append(y)
        if (I + 1) * SUB < C:
            spread.append(jnp.zeros((h, C - (I + 1) * SUB, w), _F32))
        dR.insert(0, _dot(invT[:, here], jnp.concatenate(spread, 1)))
    dR = jnp.concatenate(dR, axis=1)
    dN = -_dot(dR, X, ((2,), (2,)))
    dPkk = beta * dN
    dbeta = (jnp.sum(dN * A, 2, keepdims=True)
             + jnp.sum(dR[:, :, :dk] * Kd, 2, keepdims=True)
             + jnp.sum(dR[:, :, dk:] * v, 2, keepdims=True))
    dKd, dv = beta * dR[:, :, :dk], beta * dR[:, :, dk:]

    # the pair products: the rows' cotangents (dq_p of Aqk, dk_p of A) and
    # the keys' (kcol across sub-chunks, kin inside one). A pair (i, j)
    # adds to dg_m for j < m <= i what x_i dx_i holds of it, which is what
    # k_j dk_j holds. Summed up from the rows' parts less down from the
    # keys', every pair past m would cancel and leave its rounding: so
    # the rows' parts are summed up within their sub-chunk, which holds
    # the pair's key or comes after it, and the keys' parts of the pairs
    # across sub-chunks are summed down to the rows' sub-chunk
    rows = jax.lax.broadcasted_iota(jnp.int32, (h, C, dk), 1)
    in_sub = rows & (SUB - 1)
    rowed = jnp.exp(L)
    qr, kr = q * rowed, k * rowed
    zeros = jnp.zeros((h, SUB, dk), _F32)
    dq_p, dk_p, cols = [zeros], [zeros], []
    for I in range(1, ns):
        here = slice(I * SUB, (I + 1) * SUB)
        E = jnp.exp(jnp.where(
            rows < I * SUB, (ref[:, I * SUB:I * SUB + 1] - ref) - L,
            -jnp.inf))
        dP = jnp.concatenate([dPqk[:, here], dPkk[:, here]], axis=1)
        drow = _dot(dP, k * E)                             # (h, 2 SUB, dk)
        dq_p.append(drow[:, :SUB])
        dk_p.append(drow[:, SUB:])
        cols.append(E * _dot(dP, jnp.concatenate(
            [qr[:, here], kr[:, here]], axis=1), ((1,), (1,))))
    dq_p = rowed * jnp.concatenate(dq_p, axis=1)
    dk_p = rowed * jnp.concatenate(dk_p, axis=1)
    kcol, dg = sum(cols, jnp.zeros_like(k)), jnp.zeros_like(k)
    if cols:
        # every sub-chunk's keys' parts summed down at once
        down = _sums_before(jnp.concatenate([k * c for c in cols]),
                            jnp.concatenate([rows] * len(cols)), C)
        dg = sum(jnp.where(rows < I * SUB, down[(I - 1) * h:I * h], 0.0)
                 for I in range(1, ns))
    pair_r = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    pair_c = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    kin = jnp.zeros_like(k)
    for d in range(1, SUB):
        up_L, up_k = pltpu.roll(L, d, 1), pltpu.roll(k, d, 1)
        e = jnp.exp(jnp.where(in_sub >= d, L - up_L, -jnp.inf))
        at = pair_c == pair_r - d
        pq = jnp.sum(jnp.where(at, dPqk, 0.0), 2, keepdims=True)
        pk = jnp.sum(jnp.where(at, dPkk, 0.0), 2, keepdims=True)
        dq_p = dq_p + pq * up_k * e
        dk_p = dk_p + pk * up_k * e
        # row i's part of key i - d's cotangent
        kin = kin + pltpu.roll((pq * q + pk * k) * e, C - d, 1)
    # the pairs (i, i) of Aqk read no decay
    p0 = jnp.sum(jnp.where(pair_c == pair_r, dPqk, 0.0), 2, keepdims=True)
    dg = dg + _sums_up(q * dq_p + k * (dk_p - kin), in_sub, SUB)

    # the element-wise factors: Qd = scale q e^G and Kd = k e^G read g_m
    # in the rows from m on, Kr = k e^(G_C - G) in the rows before m,
    # gamma = e^G_C every g_m (its column turned to a row)
    dg = (dg + _sums_up(Qd * dQd + Kd * dKd, rows, C)
          + _sums_before(Kr * dKr, rows, C)
          + jnp.swapaxes(jnp.broadcast_to(dlast_col, (h, dk, C)), 1, 2))
    return (dq_p + p0 * k + scale * eG * dQd,
            dk_p + kcol + kin + p0 * q + eG * dKd + eR * dKr,
            dv, dg, dbeta, dS0)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, s_ref, ds_ref,
                *kept):
    """A group of ``n`` chunks of a block of heads of one row, a grid step
    a chunk: the first ``n`` steps make the group's chunks' factors in
    order, keep them in ``kept`` and run the chunks forward from the
    group's kept start, keeping each chunk's starting state in ``s_ref``;
    the next ``n`` walk the chunks in reverse (:func:`_chunk_bwd`) on
    ``dS`` in ``ds_ref``, which stays in VMEM from the row's last chunk to
    its first."""
    group, step = pl.program_id(2), pl.program_id(3)
    n = s_ref.shape[0]
    C = q_ref.shape[1]
    diag = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
    # beta comes and dbeta goes as rows (heads, 1, C), which HBM holds
    # without padding a column to 128 lanes; the diagonal turns them
    column = lambda row: jnp.sum(jnp.where(diag, row, 0.0), 2,
                                 keepdims=True)
    ins = lambda: (q_ref[...].astype(_F32), k_ref[...].astype(_F32),
                   v_ref[...].astype(_F32), g_ref[...],
                   column(beta_ref[...]))

    @pl.when(step == 0)
    def _():
        s_ref[0] = start_ref[...]

    @pl.when(jnp.logical_and(group == 0, step == 0))
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    @pl.when(step < n)
    def _():
        (_, Aqk, W, U0, Kr, gamma), parts = _chunk_factors(*ins())
        for ref, x in zip(kept, (Aqk, gamma) + parts):
            ref[step] = x

        @pl.when(step < n - 1)
        def _():
            S = s_ref[step]
            s_ref[step + 1] = gamma * S + _dot(Kr, U0 - _dot(W, S),
                                               ((1,), (1,)))

    @pl.when(step >= n)
    def _():
        chunk = 2 * n - 1 - step
        Aqk, gamma, *parts = (ref[chunk] for ref in kept)
        q, k, v, _, beta = ins()
        *grads, dS = _chunk_bwd(
            q, k, v, beta, Aqk, gamma, parts, s_ref[chunk], ds_ref[...],
            do_ref[...].astype(_F32))
        ds_ref[...] = dS
        dbeta = grads.pop()
        dbeta_ref[...] = jnp.sum(jnp.where(diag, dbeta, 0.0), 1,
                                 keepdims=True)
        for ref, x in zip((dq_ref, dk_ref, dv_ref, dg_ref), grads):
            ref[...] = x.astype(ref.dtype)


def _kept(heads: int, group: int, C: int, dk: int, dv: int):
    """The shapes of what :func:`_bwd_kernel` keeps of each chunk of a
    group from its forward pass to its walk back, the factors that cost
    products, in the order :func:`_chunk_factors` gives them: ``Aqk,
    gamma`` and ``ref, L, A, inv, X``."""
    return [(group, heads) + shape for shape in (
        (C, C), (dk, 1), (C, dk), (C, dk), (C, C), (C, C), (C, dk + dv))]


def _bwd_vmem_need(heads: int, C: int, dk: int, dv: int, group: int,
                   itemsize: int) -> int:
    """Bytes of VMEM a call of :func:`_backward` takes, margin included,
    as :func:`_vmem_need` counts: every block in and out twice (q, k, v,
    dO and dq, dk, dv in the compute dtype, g, dg, beta and dbeta in
    float32, a group's start), in scratch once the group's chunk states,
    ``dS`` and what it keeps of each chunk's factors, and the float32
    temporaries of one chunk's backward a head: the forward's
    :func:`_temps` and as many again."""
    tile = _flash._tile_bytes
    blocks = (4 * tile(C, dk, itemsize) + 4 * tile(C, dv, itemsize)
              + 2 * tile(C, dk, 4) + 2 * tile(1, C, 4) + tile(dk, dv, 4))
    kept = sum(math.prod(shape[:-2]) * tile(*shape[-2:], 4)
               for shape in _kept(1, group, C, dk, dv))
    return int(_flash._VMEM_MARGIN * heads * (
        2 * blocks + (group + 1) * tile(dk, dv, 4) + kept
        + 2 * _temps(C, dk, dv)))


def _backward(q, k, v, g, beta, starts, dO):
    """The backward over grouped inputs as one kernel, ``kda_bwd``: its
    grid walks every row and block of heads, its groups in reverse, and in
    each ``2 n`` steps for the group's ``n`` chunks (:func:`_bwd_kernel`).
    ``(dq, dk, dv, dg, dbeta)`` in the inputs' dtypes and layout."""
    C, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    heads, (B, blocks, groups, group) = _grid(q)
    # the inputs of chunk s forward, then of 2 n - 1 - s back; dO and the
    # gradients stay at the group's last chunk until the walk back
    ahead = lambda b, h, i, s: (groups - 1 - i, b, h,
                                jnp.minimum(s, 2 * group - 1 - s), 0, 0)
    back = lambda b, h, i, s: (groups - 1 - i, b, h,
                               jnp.minimum(group - 1, 2 * group - 1 - s),
                               0, 0)
    block = lambda d, index, rows=C: pl.BlockSpec(
        (None, None, heads, None, rows, d), index)
    need = _bwd_vmem_need(heads, C, dk, dv, group,
                          jnp.dtype(q.dtype).itemsize)
    call = pl.pallas_call(
        _bwd_kernel,
        grid=(B, blocks, groups, 2 * group),
        in_specs=[block(dk, ahead), block(dk, ahead), block(dv, ahead),
                  block(dk, ahead), block(C, ahead, 1),
                  pl.BlockSpec((None, None, heads, dk, dv),
                               lambda b, h, i, s: (groups - 1 - i, b, h, 0,
                                                   0)),
                  block(dv, back)],
        out_specs=[block(dk, back), block(dk, back), block(dv, back),
                   block(dk, back), block(C, back, 1)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g)]
        + [jax.ShapeDtypeStruct(beta[..., None, :].shape, _F32)],
        scratch_shapes=[pltpu.VMEM((group, heads, dk, dv), _F32),
                        pltpu.VMEM((heads, dk, dv), _F32)]
        + [pltpu.VMEM(shape, _F32)
           for shape in _kept(heads, group, C, dk, dv)],
        # each gradient in its input's place: a chunk's inputs are read
        # before its gradients are written, and never after
        input_output_aliases={0: 0, 1: 1, 2: 2, 3: 3},
        interpret=_flash._use_interpret(),
        name="kda_bwd",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=need if need > _flash._VMEM_DEFAULT else None),
    )
    _tracing.note_routing(kda_bwd_kernel_chunks=B * blocks * heads * groups
                          * group)
    dq, dk, dv, dg, dbeta = call(q, k, v, g, beta[..., None, :], starts,
                                 dO)
    return dq, dk, dv, dg, dbeta[..., 0, :]


@jax.custom_vjp
def _kda(q, k, v, g, beta):
    return _forward(q, k, v, g, beta)[0]


def _kda_fwd(q, k, v, g, beta):
    O, starts = _forward(q, k, v, g, beta)
    return O, (q, k, v, g, beta, starts)


def _kda_bwd(res, dO):
    q, k, v, g, beta, starts = res
    return _backward(q, k, v, g, beta, starts, dO)


_kda.defvjp(_kda_fwd, _kda_bwd)


def _layout(T: int):
    """``(chunk, group, groups, padded T)`` for a row of ``T``: a row
    shorter than a chunk is one chunk of the whole sub-chunks that hold it,
    and a row of fewer chunks than a group one group."""
    chunk = min(CHUNK, -(-T // SUB) * SUB)
    chunks = -(-T // chunk)
    group = min(GROUP, chunks)
    groups = -(-chunks // group)
    return chunk, group, groups, groups * group * chunk


def chunks_run(T: int) -> int:
    """Chunks a row of ``T`` positions is run as, the padding to whole
    groups included."""
    _, group, groups, _ = _layout(T)
    return groups * group


def saved_state_bytes(batch: int, T: int, heads: int, dk: int,
                      dv: int) -> int:
    """Bytes one :func:`chunk_kda` call keeps for its backward besides its
    inputs: the float32 state at the start of every group."""
    return _layout(T)[2] * batch * heads * dk * dv * 4


def chunk_kda(q, k, v, g, beta):
    """The gated delta rule of the module's docstring over rows of
    positions, every head on its own.

    Args:
      q, k: (batch, T, heads, dk), the compute dtype; the caller normalises
        them (KDA takes both through an L2 norm a head).
      v: (batch, T, heads, dv).
      g: (batch, T, heads, dk) log-decays (``<= 0``), float32.
      beta: (batch, T, heads) write strengths in ``[0, 1]``, float32.

    The read-out's scale is ``dk ** -0.5``. A row that is no whole number of
    groups is padded with positions that neither decay nor write.

    Returns (batch, T, heads, dv) in ``v``'s dtype. Differentiable: the
    backward is its own (the module's docstring).
    """
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    if k.shape != q.shape or v.shape[:3] != (B, T, H) or \
            g.shape != q.shape or beta.shape != (B, T, H):
        raise ValueError(
            f"chunk_kda: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, "
            f"beta {beta.shape} do not go together")
    chunk, group, groups, padded = _layout(T)

    def grouped(x):
        # (B, T, H, ...) -> (groups, B, H, group, chunk, ...)
        x = jnp.pad(x, [(0, 0), (0, padded - T)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(B, groups, group, chunk, H, *x.shape[3:])
        return jnp.moveaxis(x, (1, 4), (0, 2))

    q, k, v, g, beta = (grouped(x) for x in (q, k, v, g.astype(_F32),
                                             beta.astype(_F32)))
    heads, grid = _grid(q)
    _tracing.note_routing(kda_fwd_kernel_chunks=heads * math.prod(grid))
    o = _kda(q, k, v, g, beta)
    o = jnp.moveaxis(o, (0, 2), (1, 4)).reshape(B, padded, H, dv)
    return o[:, :T]
