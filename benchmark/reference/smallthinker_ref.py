"""The SmallThinker decoder, as published, in plain ``jax.numpy`` and
float32: forward, the next-token loss and its gradients. The yardstick the
system's outputs are held to; it shares no code with ``horovod_tpu``.

PowerInfer/SmallThinker-21BA3B-Instruct ``config.json`` (``model_name:
smallthinker_21b_instruct``) gives the shapes. Layer ``i`` on the stream
``x`` (T, d), every product without bias, RMSNorm with eps 1e-6::

    r       = x W_r                      x as it stands, before any norm
    c, g    = top6(softmax(r));  g = g / sum(g)
    u       = RMSNorm_in(x)
    q, k, v = u W_q (28 x 128), u W_k (4 x 128), u W_v (4 x 128)
    q, k    = RoPE(q, k)                 only where rope_layout[i] = 1
    see(t, j) = j <= t and (sliding_window_layout[i] = 0 or t - j < 4096)
    h       = x + softmax(q k^T / sqrt(128) over see) v W_o
    m       = RMSNorm_post(h)
    out     = h + sum_{e in c} g_e W_down,e (relu(m W_gate,e) * (m W_up,e))

one key/value head serving 7 query heads in order, RoPE rotate-half with
theta 1.5e6 over the whole head; after the last layer one more RMSNorm and
the untied head. The loss is the mean next-token cross entropy over the
``T - 1`` positions of each row.

Departures, each the configuration's own (``configs/*.json`` states them):
the chip's share of an expert-parallel deployment: the sum runs over the
chosen experts that are held (``experts_first .. + held``) while the
normalisation stays over all chosen, and what the absent experts would add
is left out; a slice of the vocabulary is the whole vocabulary. No token is
dropped: every position goes through every held expert and is weighted by
its (possibly zero) gate. So that a row of 16,384 positions fits the chip,
attention is computed one query head and ``QUERY_BLOCK`` queries at a time
(a ``[16384, 16384]`` float32 score matrix is 1.07 GB a head), the mask from
the positions of the block; the experts run one at a time; the head's loss
``QUERY_BLOCK`` positions at a time; each of these and every block under
``jax.checkpoint``. Matrix products run under
``default_matmul_precision("highest")``: on a TPU a float32 product is
otherwise rounded like bfloat16. ``dtype`` computes the whole forward in
another precision, ``router_dtype`` the router's logits alone; ``act`` and
``route_after`` compute another model (silu for relu; the router fed the
normed stream after attention, where most families put it) and
``with_inputs`` hands back what every router chose from and what every
layer's attention gave, all for the readings that set the limits
(``controls_smallthinker.py``); :func:`router_choices` is the routing
alone, again, on given inputs.
"""

import functools

import jax
import jax.numpy as jnp

STATIC = ("sliding_window_layout", "rope_layout", "sliding_window",
          "num_heads", "num_kv_heads", "eps", "rope_theta", "top_k",
          "norm_topk", "experts_first", "dtype", "router_dtype", "act",
          "route_after", "with_inputs")
QUERY_BLOCK = 2048      # queries (and positions of the head) a pass


@functools.partial(jax.jit, static_argnames=("num_layers",))
def from_system(params, num_layers):
    """The system's flax tree (``wte``, ``lm_head``, ``h<i>/...``,
    ``norm_f``) as the reference's: float32, the blocks in a list (they
    differ by layer)."""
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.asarray(x, jnp.float32), tree)
    return {"wte": f32(params["wte"]), "lm_head": f32(params["lm_head"]),
            "norm_f": f32(params["norm_f"]["scale"]),
            "h": [f32(params[f"h{i}"]) for i in range(num_layers)]}


# --------------------------------------------------------------------------
# the layer
# --------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE over (T, H, D) at positions ``0 .. T - 1``."""
    d2 = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(T):
    """Positions a pass: ``QUERY_BLOCK`` where it cuts the row into whole
    passes, else the row at once."""
    return QUERY_BLOCK if T % QUERY_BLOCK == 0 else T


def _attention(u, p, *, num_heads, num_kv_heads, rope_theta, rotated, window):
    """One row (T, d): every query head on its own and a block of queries at
    a time, so that one (block, T) score matrix is alive at a time. ``see``
    is written from positions: a key no later than the query and, with a
    ``window``, fewer than ``window`` positions back."""
    T = u.shape[0]
    hd = p["wq"]["kernel"].shape[1] // num_heads
    q = (u @ p["wq"]["kernel"]).reshape(T, num_heads, hd)
    k = (u @ p["wk"]["kernel"]).reshape(T, num_kv_heads, hd)
    v = (u @ p["wv"]["kernel"]).reshape(T, num_kv_heads, hd)
    if rotated:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    group, step = num_heads // num_kv_heads, _blocks(T)
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def queries(args):
        h, start = args
        kv = h // group
        qb = jax.lax.dynamic_slice_in_dim(q[:, h], start, step)
        t = start + jnp.arange(step)[:, None]
        see = j <= t
        if window is not None:
            see = see & (t - j < window)
        s = (qb @ k[:, kv].T) / jnp.sqrt(jnp.asarray(hd, u.dtype))
        return jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1) @ v[:, kv]

    heads, starts = jnp.meshgrid(jnp.arange(num_heads),
                                 jnp.arange(0, T, step), indexing="ij")
    o = jax.lax.map(queries, (heads.reshape(-1), starts.reshape(-1)))
    o = o.reshape(num_heads, T, hd)
    return o.transpose(1, 0, 2).reshape(T, num_heads * hd) @ p["wo"]["kernel"]


def route(x, router, *, top_k, norm_topk, router_dtype=None):
    """``(gate, choice)`` (T, top_k): softmax over all experts (in float32,
    as everything here, unless ``dtype`` or ``router_dtype`` says
    otherwise), the top ``top_k``, renormalised over the chosen ones."""
    if router_dtype is not None:        # the logits alone in that precision
        x, router = x.astype(router_dtype), router.astype(router_dtype)
    r = jax.nn.softmax((x @ router).astype(jnp.float32), axis=-1)
    gate, choice = jax.lax.top_k(r, top_k)
    if norm_topk:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return gate, choice


def _experts(m, gate, choice, p, *, experts_first, act):
    """The held experts' share for one row (T, d): a loop over the experts
    held; every position goes through each and is weighted by its gate for
    that expert, zero where it did not choose it."""
    held = p["w_gate"].shape[0]
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[act]

    @jax.checkpoint
    def one(acc, xs):
        e, w_gate, w_up, w_down = xs
        w = jnp.sum(jnp.where(choice == experts_first + e, gate, 0.0),
                    axis=-1).astype(m.dtype)
        y = (act(m @ w_gate) * (m @ w_up)) @ w_down
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]))
    return out


def _block(x, p, rotated, windowed, kw):
    """One block on rows ``x`` (B, T, d). ``(y, routed)``: ``routed`` is the
    layer's choices (B, T, top_k), with ``with_inputs`` beside what the
    router chose them from and what the attention gave, (B, T, d) each."""
    eps = kw["eps"]
    attn = {k: kw[k] for k in ("num_heads", "num_kv_heads", "rope_theta")}
    window = kw["sliding_window"] if windowed else None
    pick = {k: kw[k] for k in ("top_k", "norm_topk", "router_dtype")}

    def row(x):
        a = _attention(_rms(x, p["norm_in"]["scale"], eps), p["attn"],
                       rotated=rotated, window=window, **attn)
        h = x + a
        m = _rms(h, p["norm_post"]["scale"], eps)
        read = m if kw["route_after"] else x
        gate, choice = route(read, p["moe"]["router"], **pick)
        y = _experts(m, gate, choice, p["moe"],
                     experts_first=kw["experts_first"], act=kw["act"])
        return h + y, (choice, read, a)

    y, (choice, read, a) = jax.vmap(row)(x)
    return y, ((choice, read, a) if kw["with_inputs"] else choice)


def _layers(ref, tokens, kw):
    """``(hidden, routed, head)``: the rows after the final norm (B, T, d);
    every layer's choices stacked (L, B, T, top_k), with ``with_inputs`` a
    triple with the routers' inputs and the attention outputs (L, B, T, d);
    the head's rows."""
    dtype = jnp.dtype(kw["dtype"])
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    x = ref["wte"][tokens]
    routed = []
    for p, rotated, windowed in zip(ref["h"], kw["rope_layout"],
                                    kw["sliding_window_layout"]):
        block = jax.checkpoint(functools.partial(
            _block, rotated=bool(rotated), windowed=bool(windowed), kw=kw))
        x, kept = block(x, p)
        routed.append(kept)
    routed = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *routed)
    return _rms(x, ref["norm_f"], kw["eps"]), routed, ref["lm_head"]


def _defaults(kw):
    kw = dict(kw)
    kw.setdefault("dtype", "float32")
    kw.setdefault("router_dtype", None)
    kw.setdefault("act", "relu")
    kw.setdefault("route_after", False)
    kw.setdefault("with_inputs", False)
    return kw


def loss(ref, tokens, **kw):
    """Mean next-token cross entropy of ``tokens`` (B, T) over the ``T - 1``
    positions of each row, through the untied head, a block of positions
    at a time (the row's last position, which has no next token, weighs
    nothing)."""
    kw = _defaults(kw)
    with jax.default_matmul_precision("highest"):
        hidden, _, head = _layers(ref, tokens, kw)
        B, T, d = hidden.shape
        step = _blocks(T)
        target = jnp.roll(tokens, -1, axis=1)
        counts = (jnp.arange(T) < T - 1).astype(jnp.float32)

        @jax.checkpoint
        def part(args):
            rows, target, counts = args             # (B, step, ...)
            logp = jax.nn.log_softmax(rows @ head.T, axis=-1)
            ll = jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
            return jnp.sum(ll.astype(jnp.float32) * counts)

        cut = lambda a: jnp.moveaxis(
            a.reshape(B, T // step, step, *a.shape[2:]), 1, 0)
        total = jnp.sum(jax.lax.map(part, (
            cut(hidden), cut(target),
            jnp.broadcast_to(counts, (B, T)).reshape(B, T // step, step)
            .transpose(1, 0, 2))))
        return -total / (B * (T - 1))


@functools.partial(jax.jit, static_argnames=STATIC)
def choices(ref, tokens, **kw):
    """Every layer's choices, (L, B, T, top_k): which experts each position
    chose, held here or not. ``with_inputs``: ``(choices, inputs,
    attention)``, the routers' inputs and the attention outputs (L, B, T, d)
    beside them."""
    kw = _defaults(kw)
    with jax.default_matmul_precision("highest"):
        return _layers(ref, tokens, kw)[1]


@functools.partial(jax.jit,
                   static_argnames=("top_k", "norm_topk", "router_dtype"))
def router_choices(inputs, routers, *, top_k, norm_topk=True,
                   router_dtype=None):
    """The routing alone, again, on given inputs: ``inputs`` (L, ..., d) in
    whatever precision they were computed, ``routers`` (L, d, experts); the
    choices (L, ..., top_k) of a float32 router on exactly these inputs.
    What a side's own choices are held to when the question is the router's
    precision and not that of what came before it."""
    def layer(xs):
        x, router = xs
        return route(x.astype(jnp.float32), router, top_k=top_k,
                     norm_topk=norm_topk, router_dtype=router_dtype)[1]
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(layer, (inputs, routers))


@functools.partial(jax.jit, static_argnames=STATIC, donate_argnums=(1, 2))
def ref_microbatch(ref, loss_sum, grad_sum, tokens, **kw):
    """Add one micro-batch's loss and gradients to the running sums."""
    value, grads = jax.value_and_grad(loss)(ref, tokens, **kw)
    return loss_sum + value, jax.tree_util.tree_map(jnp.add, grad_sum, grads)


@jax.jit
def ref_norm(grad_sum, n):
    return jnp.sqrt(sum(jnp.sum((g / n) ** 2) for g in
                        jax.tree_util.tree_leaves(grad_sum)))


def loss_and_grad(ref, tokens, *, micro=1, **kw):
    """``(loss, gradients)`` of the whole batch, taken in micro-batches of
    ``micro`` rows (equal sizes, so the batch mean is the mean of the
    micro-batch means); the gradients in the reference's own tree."""
    if tokens.shape[0] % micro:
        raise ValueError(f"batch {tokens.shape[0]} is no multiple of {micro}")
    n = tokens.shape[0] // micro
    loss_sum = jnp.zeros((), jnp.float32)
    grad_sum = jax.tree_util.tree_map(jnp.zeros_like, ref)
    for i in range(n):
        loss_sum, grad_sum = ref_microbatch(
            ref, loss_sum, grad_sum, tokens[i * micro:(i + 1) * micro], **kw)
    return float(loss_sum) / n, jax.tree_util.tree_map(
        lambda g: g / n, grad_sum)


def loss_and_grad_norm(ref, tokens, *, micro=1, **kw):
    """Loss of the whole batch and the norm of its gradient."""
    value, grads = loss_and_grad(ref, tokens, micro=micro, **kw)
    return value, float(ref_norm(grads, jnp.float32(1)))
