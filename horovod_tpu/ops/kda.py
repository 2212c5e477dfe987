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

Everything that does not read ``S0`` is the chunk's own (:func:`_intra`);
what reads it is :func:`_step`. A position's log-decay may reach -1.6 and a
chunk's sum -100, so ``exp(-G)`` alone would overflow float32: the factor
``exp(G_i - G_j)`` is only ever formed where ``j <= i``, inside sub-chunks of
:data:`SUB` positions element by element, and across sub-chunks split at the
last position before the later one, where both halves are ``<= 1``.

The forward is one Pallas kernel, ``kda_fwd`` (:func:`_forward`): its grid
walks the chunks of each row and block of :data:`HEADS` heads in order, a
grid step makes one chunk's factors for the block (:func:`_chunk_factors`,
:func:`_intra`'s steps laid out for Mosaic, every product batched over the
heads) and runs :func:`_step` on their states, which stay in VMEM scratch
from the row's first chunk to its last. The chunks go in groups
(:data:`GROUP` chunks of :data:`CHUNK` positions) and the forward keeps the
state at the start of every group (``saved_state_bytes`` counts it). The
backward is plain XLA: it walks the groups in reverse, carrying ``dS``: it
makes a group's factors again under ``jax.vjp`` of :func:`_intra` for the
group's chunks at once, runs the group's chunks forward from the kept state
to get each chunk's ``S0``, walks them in reverse (:func:`_step_bwd`), and
pulls the factors' cotangents back to ``q, k, v, g, beta``. No position is
visited one at a time.

The state and all decay arithmetic are float32, every product at full
precision (the kernel's as XLA's, :func:`_mm`); ``q``, ``k``, ``v`` come in
the compute dtype and the output leaves in ``v``'s. The chunk's triangular
system is solved by blocks (:func:`_solve`). Off a TPU the kernel runs in the
Pallas interpreter, as the flash kernels do.
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
HEADS = 8       # heads a grid step of the forward kernel (as many as divide H)

_F32 = jnp.float32


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
    :data:`SUB`: each diagonal block's inverse as ``(I - D)(I + D^2)(I +
    D^4)(I + D^8)`` (``D^SUB = 0``), then forward substitution over the
    block rows by products. XLA's triangular solve walks the chunk's 64
    rows one at a time and took 30 % of the layer on the v5e (PERF.md,
    Findings PR 39)."""
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


# ---------------------------------------------------------------------------
# the forward kernel
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
    """:func:`_intra` for one chunk of several heads inside the kernel:
    ``q``, ``k`` (heads, C, dk), ``v`` (heads, C, dv), ``g`` (heads, C,
    dk), ``beta`` (heads, C, 1), all float32. The same factors by the same
    steps (the cumulative log-decays ``G``, sub-chunks of :data:`SUB`,
    ``exp(G_i - G_j)`` formed only where ``j <= i``, the system solved by
    blocks of :data:`SUB`), laid out for Mosaic: the pairs inside a
    sub-chunk are taken a diagonal ``i - j`` at a time (the row ``i - j``
    positions up, a rotation) and no array is sliced along lanes. Returns
    ``(Qd, Aqk, W, U0, Kr, gamma)``, ``gamma`` a column ``(heads, dk,
    1)``."""
    h, C, dk = k.shape
    scale = dk ** -0.5
    rows = jax.lax.broadcasted_iota(jnp.int32, (h, C, dk), 1)
    in_sub = rows & (SUB - 1)                   # a row's place in its sub-chunk
    G = _sums_down(g, rows, C)
    # G at the position before a row's sub-chunk (0 in the first): each
    # sub-chunk's last row moved one down, then spread over its sub-chunk
    ends = jnp.where(in_sub == SUB - 1, G, 0.0)
    ref = _sums_down(jnp.where(rows >= 1, pltpu.roll(ends, 1, 1), 0.0),
                     in_sub, SUB)
    last = G[:, C - 1:C]                                        # (h, 1, dk)
    Qd = scale * q * jnp.exp(G)
    Kd = k * jnp.exp(G)
    Kr = k * jnp.exp(last - G)
    rowed = jnp.exp(G - ref)                                    # <= 1

    # across sub-chunks: sub-chunk I's rows against the positions before it
    # (h, C, C); sub-chunk 0 has none
    zeros = jnp.zeros((h, SUB, C), _F32)
    off_qk, off_kk = [zeros], [zeros]
    for I in range(1, C // SUB):
        here = slice(I * SUB, (I + 1) * SUB)
        cols = k * jnp.exp(jnp.where(rows < I * SUB,
                                     ref[:, I * SUB:I * SUB + 1] - G,
                                     -jnp.inf))                 # <= 1, or 0
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
        up_G, up_k = ((G, k) if d == 0 else
                      (pltpu.roll(G, d, 1), pltpu.roll(k, d, 1)))
        ke = up_k * jnp.exp(jnp.where(in_sub >= d, G - up_G, -jnp.inf))
        at = pair_c == pair_r - d
        Aqk = Aqk + jnp.where(at, jnp.sum(q * ke, 2, keepdims=True), 0.0)
        if d:
            A = A + jnp.where(at, jnp.sum(k * ke, 2, keepdims=True), 0.0)
    Aqk = scale * Aqk

    # (I + diag(beta) A) [W | U0] = diag(beta) [Kd | V], by blocks as
    # _solve: the diagonal blocks' inverses all at once (zero elsewhere),
    # then the block rows in order
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
    return (Qd, Aqk, solved[:, :, :dk], solved[:, :, dk:], Kr,
            jnp.exp(last_col))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, starts_ref,
                s_ref):
    """One chunk of a block of heads of one row: the heads' factors, then
    :func:`_step` on their states in ``s_ref``, which stay in VMEM from the
    row's first chunk to its last; at a group's first chunk the states are
    also its ``starts``. Every operation takes the block's heads at once."""
    group, chunk = pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(group == 0, chunk == 0))
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(chunk == 0)
    def _():
        starts_ref[...] = s_ref[...]

    Qd, Aqk, W, U0, Kr, gamma = _chunk_factors(
        q_ref[...].astype(_F32), k_ref[...].astype(_F32),
        v_ref[...].astype(_F32), g_ref[...], beta_ref[...])
    C, S = Qd.shape[1], s_ref[...]
    both = _dot(jnp.concatenate([W, Qd], axis=1), S)   # W S and Qd S at once
    U = U0 - both[:, :C]
    o_ref[...] = (both[:, C:] + _dot(Aqk, U)).astype(o_ref.dtype)
    s_ref[...] = gamma * S + _dot(Kr, U, ((1,), (1,)))


def _vmem_need(heads: int, C: int, dk: int, dv: int, itemsize: int) -> int:
    """Bytes of VMEM a call of :func:`_forward` takes, margin included, as
    ``ops/flash_attention._vmem_need`` counts: every block in and out twice
    (q, k, v in the compute dtype, g and beta in float32 | O, a group's
    starts), the states in scratch once, and the float32 temporaries of
    one chunk's factors a head: a dozen ``(C, d)`` and ``(C, C)`` arrays
    and the system's right-hand side."""
    tile = _flash._tile_bytes
    blocks = (2 * tile(C, dk, itemsize) + 2 * tile(C, dv, itemsize)
              + tile(C, dk, 4) + tile(C, 1, 4) + tile(dk, dv, 4))
    temps = (12 * tile(C, dk, 4) + 8 * tile(C, C, 4)
             + 3 * tile(C, dk + dv, 4) + 2 * tile(2 * C, dv, 4))
    return int(_flash._VMEM_MARGIN * heads * (2 * blocks + tile(dk, dv, 4)
                                              + temps))


def _grid(q):
    """``(heads a grid step, grid)`` of the forward kernel over grouped
    inputs ``(groups, B, H, group, C, dk)``: every row and block of heads,
    and its chunks in order, a group and the chunks in it."""
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


@jax.custom_vjp
def _kda(q, k, v, g, beta):
    return _forward(q, k, v, g, beta)[0]


def _kda_fwd(q, k, v, g, beta):
    O, starts = _forward(q, k, v, g, beta)
    return O, (q, k, v, g, beta, starts)


def _kda_bwd(res, dO):
    q, k, v, g, beta, starts = res

    def body(dS, xs):
        grp, S, dO_g = xs
        factors, pull = jax.vjp(_intra, *grp)
        factors = _chunks_first(factors)
        # each chunk's S0, from the state kept at the group's start
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
