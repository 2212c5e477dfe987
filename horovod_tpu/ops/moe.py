"""Mixture-of-Experts with expert parallelism (the ``ep`` mesh axis).

The reference stack reaches MoE scale through NCCL all-to-all in
Megatron/DeepSpeed layers built on top of hvd; here expert parallelism is a
first-class mesh axis. TPU-first design (Switch Transformer / GShard lineage,
PAPERS.md):

- Routing is the classic one-hot dispatch/combine einsum formulation —
  static shapes only (capacity-bounded), so the whole layer traces into one
  XLA program. No gather/scatter with dynamic shapes.
- Expert weights carry a leading ``num_experts`` dim sharded over ``ep``
  (see ``models/gpt2.partition_rules``); the dispatch einsum then contracts a
  token-sharded operand against an expert-sharded operand and GSPMD inserts
  the all-to-all over ICI — the same comm pattern the reference gets from
  NCCL alltoall, derived by the compiler instead of hand-written.
- Router math in fp32 (logits/softmax are precision-sensitive), expert FFN
  in bf16 on the MXU.
- Auxiliary load-balance loss (Switch eq. 4) keeps routing uniform; it is
  returned so the model can add it to the objective.

Beside the capacity routers stands the dropless layer, ``RoutedExperts``
(``routed_share`` is its arithmetic): top-k of many experts, no capacity
and no dropped assignment, for models whose published routing drops nothing
and whose references therefore drop nothing. It is told which experts it
holds, routes over all of them and computes the part of the result that its
own experts give: the local assignments are sorted by expert and go through
three grouped products (``jax.lax.ragged_dot``: XLA's own grouped kernel on
a TPU) a window of rows at a time: twice the rows its share of the experts
can expect, of which the kernel visits those the groups cover. A routing
that gives more goes over as many windows as it needs, up to the worst
case of ``positions x min(top_k, held)`` rows, by a loop whose length the
device counts. Under an ``ep`` mesh axis
the positions of all peers are gathered, every peer computes its experts'
share of all of them, and a reduce-scatter sums the shares; on one device it
runs without that exchange. Its routing rule is the softmax one (scores over
all experts, the top-k of them, gates the chosen scores renormalised) unless
told otherwise: ``score="sigmoid"``, a ``select_bias`` that enters the choice
and not the gates, the normaliser's epsilon and a scaling factor give the
rule of the families that balance their experts by such a bias. A family's
shared expert is a sibling, ``SharedExpert``: every position goes through
it, so every holder computes it alike on its own positions and it is no part
of a share: nothing of it is exchanged, and shares summed count it once.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu import tracing as _tracing

__all__ = ["Top1Router", "Top2Router", "MoEMLP",
           "switch_load_balance_loss", "RoutedExperts", "routed_share",
           "row_bounds", "SharedExpert"]


def switch_load_balance_loss(router_probs: jnp.ndarray,
                             expert_index: jnp.ndarray) -> jnp.ndarray:
    """Switch Transformer aux loss: E * sum_e f_e * P_e.

    f_e = fraction of tokens routed to expert e, P_e = mean router prob for
    e. Minimised (= 1) at uniform routing.

    Args:
      router_probs: (N, E) fp32 softmax outputs.
      expert_index: (N,) int32 argmax expert per token.
    """
    num_experts = router_probs.shape[-1]
    f = jnp.mean(jax.nn.one_hot(expert_index, num_experts, dtype=jnp.float32),
                 axis=0)
    p = jnp.mean(router_probs, axis=0)
    return num_experts * jnp.sum(f * p)


class Top1Router(nn.Module):
    """Switch-style top-1 router with static capacity.

    Produces one-hot dispatch/combine tensors of shape (N, E, C): token n
    goes to slot c of expert e. Tokens over capacity are dropped (their
    combine weights are zero → they pass through the residual unchanged),
    exactly the Switch semantics.
    """
    num_experts: int
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        n, d = x.shape
        e = self.num_experts
        c = max(1, int(self.capacity_factor * n / e))

        router = self.param("router", nn.initializers.normal(0.02), (d, e),
                            jnp.float32)
        logits = x.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        expert_index = jnp.argmax(probs, axis=-1)
        expert_gate = jnp.max(probs, axis=-1)

        onehot = jax.nn.one_hot(expert_index, e, dtype=jnp.float32)
        # Position of each token within its expert's queue (0-based).
        position_in_expert = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot
        within_capacity = position_in_expert < c
        onehot = onehot * within_capacity

        # (N, E, C) one-hot over capacity slots.
        slot = jax.nn.one_hot(
            jnp.sum(position_in_expert, axis=-1).astype(jnp.int32), c,
            dtype=jnp.float32)
        dispatch = onehot[..., None] * slot[:, None, :]
        combine = expert_gate[:, None, None] * dispatch

        aux_loss = switch_load_balance_loss(probs, expert_index)
        return dispatch, combine, aux_loss


class Top2Router(nn.Module):
    """GShard-style top-2 router with static capacity.

    Each token is sent to its two highest-probability experts with gates
    renormalized over the pair (``g1/(g1+g2)``, ``g2/(g1+g2)``). Capacity
    slots are assigned top-1 choices first, then top-2 choices fill the
    remainder (GShard's ordering, so second choices are the ones dropped
    under pressure). Returns the same ``(dispatch, combine, aux)``
    contract as :class:`Top1Router` — (N, E, C) tensors — so ``MoEMLP``
    uses either router unchanged.
    """
    num_experts: int
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        n, d = x.shape
        e = self.num_experts
        # GShard sizes capacity for two assignments per token.
        c = max(1, int(self.capacity_factor * 2 * n / e))

        router = self.param("router", nn.initializers.normal(0.02), (d, e),
                            jnp.float32)
        logits = x.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)

        idx1 = jnp.argmax(probs, axis=-1)
        gate1 = jnp.max(probs, axis=-1)
        probs2 = probs * (1.0 - jax.nn.one_hot(idx1, e, dtype=jnp.float32))
        idx2 = jnp.argmax(probs2, axis=-1)
        gate2 = jnp.max(probs2, axis=-1)
        denom = jnp.maximum(gate1 + gate2, 1e-9)
        gate1, gate2 = gate1 / denom, gate2 / denom

        one1 = jax.nn.one_hot(idx1, e, dtype=jnp.float32)
        one2 = jax.nn.one_hot(idx2, e, dtype=jnp.float32)
        # Slot positions: top-1 queue first, top-2 continues the counts.
        pos1 = (jnp.cumsum(one1, axis=0) - 1.0) * one1
        count1 = jnp.sum(one1, axis=0)                     # (E,)
        pos2 = ((jnp.cumsum(one2, axis=0) - 1.0) + count1[None]) * one2
        one1 = one1 * (pos1 < c)
        one2 = one2 * (pos2 < c)

        def slots(onehot, pos):
            s = jax.nn.one_hot(
                jnp.sum(pos, axis=-1).astype(jnp.int32), c,
                dtype=jnp.float32)
            return onehot[..., None] * s[:, None, :]

        d1 = slots(one1, pos1)
        d2 = slots(one2, pos2)
        dispatch = d1 + d2
        combine = gate1[:, None, None] * d1 + gate2[:, None, None] * d2

        aux_loss = switch_load_balance_loss(probs, idx1)
        return dispatch, combine, aux_loss


class MoEMLP(nn.Module):
    """Expert-parallel MLP block: drop-in for a transformer's dense FFN.

    Returns ``(out, aux_loss)``; callers add ``aux_loss`` (scaled by
    ``aux_loss_weight``, typically 1e-2) to the training objective.
    """
    num_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16
    # "top1" (Switch) or "top2" (GShard); same dispatch/combine contract.
    router_type: str = "top1"
    # "gelu": 2-matrix biased FFN experts (Switch/GShard). "swiglu":
    # bias-free 3-matrix gated experts (the Mixtral shape — pair with
    # router_type="top2" for the full recipe).
    activation: str = "gelu"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        b, t, d = x.shape
        e, f = self.num_experts, self.d_ff
        tokens = x.reshape(b * t, d)

        if self.router_type == "top1":
            router_cls = Top1Router
        elif self.router_type == "top2":
            router_cls = Top2Router
        else:
            raise ValueError(f"unknown router_type {self.router_type!r}; "
                             "expected 'top1' or 'top2'")
        dispatch, combine, aux_loss = router_cls(
            self.num_experts, self.capacity_factor, name="router")(tokens)

        if self.activation not in ("gelu", "swiglu"):
            raise ValueError(f"unknown activation {self.activation!r}; "
                             "expected 'gelu' or 'swiglu'")
        w_in = self.param("w_in", nn.initializers.lecun_normal(), (e, d, f),
                          jnp.float32)
        w_out = self.param("w_out", nn.initializers.lecun_normal(), (e, f, d),
                           jnp.float32)
        if self.activation == "swiglu":
            w_gate = self.param("w_gate", nn.initializers.lecun_normal(),
                                (e, d, f), jnp.float32)
        else:
            b_in = self.param("b_in", nn.initializers.zeros, (e, f),
                              jnp.float32)
            b_out = self.param("b_out", nn.initializers.zeros, (e, d),
                               jnp.float32)

        # Dispatch: (N, E, C) x (N, D) -> (E, C, D). Contracting the
        # token-sharded axis against expert-sharded weights is where GSPMD
        # inserts the ep all-to-all.
        expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(self.dtype),
                               tokens.astype(self.dtype))
        if self.activation == "swiglu":
            g = jnp.einsum("ecd,edf->ecf", expert_in,
                           w_gate.astype(self.dtype))
            u = jnp.einsum("ecd,edf->ecf", expert_in,
                           w_in.astype(self.dtype))
            h = nn.silu(g) * u
            expert_out = jnp.einsum("ecf,efd->ecd", h,
                                    w_out.astype(self.dtype))
        else:
            h = jnp.einsum("ecd,edf->ecf", expert_in,
                           w_in.astype(self.dtype)) + b_in[:, None].astype(
                               self.dtype)
            h = nn.gelu(h)
            expert_out = jnp.einsum("ecf,efd->ecd", h,
                                    w_out.astype(self.dtype)) + b_out[
                                        :, None].astype(self.dtype)
        # Combine back to token order; dropped tokens get zeros.
        out = jnp.einsum("nec,ecd->nd", combine.astype(self.dtype),
                         expert_out)
        return out.reshape(b, t, d), aux_loss


# ---------------------------------------------------------------------------
# the dropless layer
# ---------------------------------------------------------------------------

def _int_zero(x):
    """The cotangent of an integer or boolean argument."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


# A window is this many times the local assignments that a holder expects
# of a uniform router, in whole tiles of rows. A constant: the benchmark's
# cells fill half a window.
_WINDOW_OVER_EXPECTED = 2
_ROW_TILE = 512


def row_bounds(n: int, top_k: int, held: int, experts_total: int
               ) -> Tuple[int, int]:
    """``(tight, rows)`` of a share of ``n`` positions. ``rows = n *
    min(top_k, held)`` is the worst case, every position choosing only
    experts held here; ``tight``, the rows every ``d``-wide operation of
    the share is shaped for, is twice what a holder of ``held`` of
    ``experts_total`` experts expects, in whole tiles of 512 rows, and
    never more than ``rows`` (a holder of every expert has the one
    bound)."""
    rows = n * min(top_k, held)
    expected = _WINDOW_OVER_EXPECTED * n * top_k * held // experts_total
    return min(rows, -(-expected // _ROW_TILE) * _ROW_TILE), rows


_ACTS = {"silu": nn.silu, "relu": nn.relu}


def _ffn(xs, w_gate, w_up, w_down, sizes, act: str = "silu"):
    """The gated unit of each row's expert (SwiGLU, or ReGLU with
    ``act="relu"``): three grouped products over rows sorted by expert,
    ``sizes`` of them for each. What the kernel leaves in the rows past the
    groups is nobody's promise."""
    g = jax.lax.ragged_dot(xs, w_gate, sizes)
    u = jax.lax.ragged_dot(xs, w_up, sizes)
    return jax.lax.ragged_dot(_ACTS[act](g) * u, w_down, sizes)


def _window(r, top_k, start, order, group_sizes):
    """Rows ``start .. start + r - 1`` of the sorted assignments: the flat
    assignment and the position of each, which of them hold a local
    assignment, and the rows of each held expert among them."""
    src = jax.lax.dynamic_slice(order, (start,), (r,))
    ends = jnp.cumsum(group_sizes)
    sizes = (jnp.clip(ends - start, 0, r)
             - jnp.clip(ends - group_sizes - start, 0, r))
    live = jnp.arange(r, dtype=jnp.int32) < jnp.sum(sizes)
    return src, src // top_k, live[:, None], sizes


def _over_windows(r, args, body, sums):
    """``body(start, carry)`` for every window of ``r`` rows that holds a
    local assignment, as many as this routing gave, counted on the device;
    the carry starts as zeros of the ``(shape, dtype)`` pairs ``sums``.
    Where one window covers the worst case, it is called once. ``args`` are
    all that ``body`` reads: inside ``shard_map`` a loop's carry must vary
    over the mesh axes from the start that it varies over at the end."""
    order, group_sizes = args[:2]
    varies = tuple(frozenset().union(*(jax.typeof(a).vma for a in args)))
    carry = tuple(jnp.zeros(shape, dtype) for shape, dtype in sums)
    if varies:
        carry = jax.lax.pcast(carry, varies, to="varying")
    if r >= order.shape[0]:
        return body(0, carry)
    total = jnp.sum(group_sizes)
    return jax.lax.while_loop(
        lambda c: c[0] < total, lambda c: (c[0] + r, body(*c)),
        (jnp.int32(0), carry))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _share(r, top_k, act, order, group_sizes, x, w, w_gate, w_up, w_down):
    """The share of ``x`` (n, d), ``r`` sorted assignments at a time:
    gather the rows, the experts' gated unit, and the gated rows summed into
    their positions in fp32. ``order`` (whole windows) is the flat
    assignments sorted by held expert, ``w`` (n, top_k) fp32 their gates,
    zero on an assignment of another holder; ``x`` and the weights are in
    the compute dtype.

    Differentiated as a whole, with its arguments as the residuals: the
    backward pass goes over the same windows again, and nothing the size
    of the worst case is ever written."""
    def body(start, carry):
        src, pos, live, sizes = _window(r, top_k, start, order, group_sizes)
        ys = _ffn(x[pos], w_gate, w_up, w_down, sizes, act)
        gated = w.reshape(-1)[src][:, None] * ys.astype(jnp.float32)
        return (carry[0].at[pos].add(jnp.where(live, gated, 0)),)

    args = (order, group_sizes, x, w, w_gate, w_up, w_down)
    return _over_windows(r, args, body, [(x.shape, jnp.float32)]
                         )[0].astype(x.dtype)


def _share_fwd(r, top_k, act, *args):
    return _share(r, top_k, act, *args), args


def _share_bwd(r, top_k, act, args, g):
    order, group_sizes, x, w, w_gate, w_up, w_down = args

    def body(start, grads):
        dx, dw, *dweights = grads
        src, pos, live, sizes = _window(r, top_k, start, order, group_sizes)
        ys, back = jax.vjp(functools.partial(_ffn, sizes=sizes, act=act),
                           x[pos], w_gate, w_up, w_down)
        g_rows = g[pos].astype(jnp.float32)
        dxs, *more = back(jnp.where(
            live, g_rows * w.reshape(-1)[src][:, None], 0
        ).astype(ys.dtype))
        gate_rows = jnp.where(live, g_rows * ys.astype(jnp.float32), 0)
        return (dx.at[pos].add(jnp.where(live, dxs.astype(jnp.float32), 0)),
                dw.at[src].add(jnp.sum(gate_rows, axis=-1)),
                *(a + b for a, b in zip(dweights, more)))

    # the rows' cotangents are summed into positions in fp32; a window's
    # weight gradients come out of the grouped products in the compute
    # dtype and are added in it
    grads = _over_windows(r, args + (g,), body, [
        (x.shape, jnp.float32), ((w.size,), jnp.float32),
        *((a.shape, a.dtype) for a in (w_gate, w_up, w_down))])
    return (_int_zero(order), _int_zero(group_sizes)) + tuple(
        d.reshape(a.shape).astype(a.dtype) for d, a in zip(grads, args[2:]))


_share.defvjp(_share_fwd, _share_bwd)


def routed_share(tokens, router, w_gate, w_up, w_down, *, first, top_k: int,
                 norm_topk: bool = True, dtype=jnp.bfloat16,
                 score: str = "softmax", select_bias=None,
                 norm_eps: float = 0.0, scale: float = 1.0,
                 route_from=None, act: str = "silu"):
    """The part of a dropless top-k expert layer that the experts held here
    give, for ``tokens`` (n, d).

    ``router`` (d, experts_total) scores every expert in fp32 (softmax over
    all of them, the top ``top_k``, renormalised over the chosen ones when
    ``norm_topk``); ``w_gate``/``w_up`` (held, d, f) and ``w_down`` (held, f,
    d) are experts ``first .. first + held - 1`` (``first`` may be traced:
    an ``ep`` peer's index times ``held``). The routing rule has four more
    arguments, whose defaults are the rule above and add nothing to its
    trace: ``score="sigmoid"`` scores each expert on its own;
    ``select_bias`` (experts_total,) fp32 is added to the scores for the
    choice alone (the gates stay the unbiased scores of the chosen; it
    takes no gradient); ``norm_eps`` is added to the normaliser;
    ``scale`` multiplies the gates. Two more say what the layer is made
    of: ``route_from`` (n, d) is what the router reads where that is not
    what the experts read (a route made from a block's input, before its
    attention, for rows that come after it): the choice and the gates come
    from it and the router's gradient goes to it, the rows' to ``tokens``;
    ``act`` is the experts' gate activation, ``"silu"`` (SwiGLU) or
    ``"relu"`` (ReGLU). Returns ``(out, aux)``:
    ``out[p] = sum_{e chosen by p and held} gate[p, e] * down_e(act(gate_e
    x) * up_e x)`` in ``dtype``; the normalisation stays over all chosen
    experts, held or not, so that the shares of all holders add up to the
    whole layer. ``aux`` holds ``group_sizes`` (held,), the rows each held
    expert was given, and ``choice`` (n, top_k), the experts chosen.

    No assignment is dropped, and the rows are shaped for what the share
    can expect, not for the worst case: the gather, the grouped products
    and the sum back into positions work on a window of ``tight`` sorted
    assignments (:func:`row_bounds`), and the rows this routing gave
    (``group_sizes``, on the device) say how many windows there are: one
    where they fit it, as many as it takes up to the worst case ``n *
    min(top_k, held)`` where they do not. No ``d``-wide operation is
    shaped by ``n * top_k``.
    """
    n, d = tokens.shape
    held = w_gate.shape[0]
    if act not in _ACTS:
        raise ValueError(f"unknown act {act!r}; expected one of "
                         f"{sorted(_ACTS)}")
    read = tokens if route_from is None else route_from
    if read.shape != tokens.shape:
        raise ValueError(f"route_from {read.shape} is not the tokens' "
                         f"{tokens.shape}")
    with _tracing.scope("moe/route"):
        logits = jnp.dot(read.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        if score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        elif score == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"unknown score {score!r}; expected 'softmax' "
                             "or 'sigmoid'")
        if select_bias is None:
            gate, choice = jax.lax.top_k(probs, top_k)          # (n, k)
        else:
            _, choice = jax.lax.top_k(
                probs + jax.lax.stop_gradient(select_bias), top_k)
            gate = jnp.take_along_axis(probs, choice, axis=-1)
        if norm_topk:
            total = jnp.sum(gate, axis=-1, keepdims=True)
            gate = gate / (total + norm_eps if norm_eps else total)
        if scale != 1.0:
            gate = gate * scale
        local = choice - first
        mine = (local >= 0) & (local < held)
        # the assignments sorted by held expert, those of other holders
        # last: the local ones are the first ``sum(group_sizes)``
        key = jnp.where(mine, local, held).reshape(-1).astype(jnp.int32)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)
        w = jnp.where(mine, gate, 0.0)
    tight, rows = row_bounds(n, top_k, held, router.shape[1])
    # whole windows, as many as cover the worst case: the local
    # assignments lie inside the first ``rows``
    cover = -(-rows // tight) * tight
    # the scope holds all of the share, forward and backward (a custom_vjp's
    # backward is named by where it was called): the casts of the weights,
    # the loop over windows with its carries, and what a window does
    with _tracing.scope("moe/experts"):
        order = jnp.pad(order, (0, max(0, cover - order.size)))[:cover]
        out = _share(tight, top_k, act, order, group_sizes,
                     tokens.astype(dtype),
                     w, w_gate.astype(dtype), w_up.astype(dtype),
                     w_down.astype(dtype))
    return out, {"group_sizes": group_sizes, "choice": choice}


class RoutedExperts(nn.Module):
    """Dropless top-k routed gated experts (SwiGLU, or ReGLU with
    ``act="relu"``; no bias, no auxiliary loss; a
    shared expert is :class:`SharedExpert`, beside it), told which experts
    it holds: ``experts_held = (first,
    count)`` of ``experts_total``. The router keeps its full width. With
    ``ep_axis`` (inside ``shard_map`` over that mesh axis, positions and
    experts sharded over it) peer ``i`` holds experts ``i * count ..``, the
    positions of all peers are gathered, each peer computes its share of
    all of them and a reduce-scatter sums the shares into each peer's own
    positions. Without it nothing is exchanged and the result is this
    holder's share alone: what the absent experts would add is left out.

    No assignment is dropped. A share is shaped for twice the rows a
    holder of ``count`` of ``experts_total`` experts expects
    (:func:`row_bounds`), which a step runs at; where a routing gives more,
    up to the worst case of every position choosing only experts held
    here, it goes over a second window of rows, and a third, by a loop on
    the device (under ``ep_axis`` each peer by its own rows: the loop holds
    no collective). A holder of every expert has the one bound and no loop.

    ``score``, ``norm_eps`` and ``scale`` are the routing rule's
    (:func:`routed_share`); ``select_bias`` (experts_total,) is handed to
    the call, because it is a buffer that its holder keeps and moves, not a
    parameter: it has no gradient and no optimizer state. ``route_from``
    (B, T, D), also handed to the call, is what the router reads where that
    is not ``x`` (:func:`routed_share`); under ``ep_axis`` it is gathered
    beside ``x``, a second stream of the same size.

    Returns ``out`` (B, T, D); ``group_sizes`` and ``choice`` are sown into
    the ``"intermediates"`` collection.
    """
    experts_total: int
    experts_held: Tuple[int, int]
    top_k: int
    d_ff: int
    norm_topk: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    ep_axis: Optional[str] = None
    score: str = "softmax"
    norm_eps: float = 0.0
    scale: float = 1.0
    act: str = "silu"

    @nn.compact
    def __call__(self, x: jnp.ndarray, select_bias=None,
                 route_from=None) -> jnp.ndarray:
        b, t, d = x.shape
        first, held = self.experts_held
        if not 0 <= first <= first + held <= self.experts_total or held < 1:
            raise ValueError(
                f"experts_held={self.experts_held} is no range of the "
                f"{self.experts_total} experts")
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        router = self.param("router", nn.initializers.normal(0.02),
                            (d, self.experts_total), jnp.float32)
        w_gate = self.param("w_gate", init, (held, d, self.d_ff),
                            jnp.float32)
        w_up = self.param("w_up", init, (held, d, self.d_ff), jnp.float32)
        w_down = self.param("w_down", init, (held, self.d_ff, d),
                            jnp.float32)
        share = functools.partial(
            routed_share, router=router, w_gate=w_gate, w_up=w_up,
            w_down=w_down, top_k=self.top_k, norm_topk=self.norm_topk,
            dtype=self.dtype, score=self.score, select_bias=select_bias,
            norm_eps=self.norm_eps, scale=self.scale, act=self.act)
        tokens = x.reshape(b * t, d)
        if route_from is not None:
            route_from = route_from.reshape(b * t, d)
        if self.ep_axis is None:
            out, aux = share(tokens, first=first, route_from=route_from)
        else:
            everyone = lambda rows: jax.lax.all_gather(
                rows, self.ep_axis, axis=0, tiled=True)
            out, aux = share(
                everyone(tokens),
                first=jax.lax.axis_index(self.ep_axis) * held,
                route_from=(None if route_from is None
                            else everyone(route_from)))
            out = jax.lax.psum_scatter(out, self.ep_axis,
                                       scatter_dimension=0, tiled=True)
        tight, rows = row_bounds(aux["choice"].shape[0], self.top_k, held,
                                 self.experts_total)
        _tracing.note_routing(moe_rows_tight=tight, moe_rows_bound=rows)
        self.sow("intermediates", "group_sizes", aux["group_sizes"])
        self.sow("intermediates", "choice", aux["choice"])
        return out.reshape(b, t, d)


class SharedExpert(nn.Module):
    """The expert that no router chooses: one bias-free SwiGLU of width
    ``d_ff`` that every position goes through, unweighted, beside the
    routed ones (``x + RoutedExperts(...)(u) + SharedExpert(...)(u)``). It
    is whole on every holder of a share and works on that holder's own
    positions, outside :class:`RoutedExperts` and so outside what its
    ``ep_axis`` gathers and reduce-scatters: the shares of all holders plus
    this **once** are the whole layer. At the dense width it is also a
    family's dense feed-forward."""
    d_ff: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        dense = lambda width, name: nn.Dense(width, use_bias=False,
                                             dtype=self.dtype, name=name)
        return dense(x.shape[-1], "w_down")(
            nn.silu(dense(self.d_ff, "w_gate")(x))
            * dense(self.d_ff, "w_up")(x))
