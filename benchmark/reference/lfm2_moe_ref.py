"""The LFM2 hybrid decoder with routed experts, as published, in plain
``jax.numpy`` and float32: forward, the next-token loss and its gradients.
The yardstick the system's outputs are held to; it shares no code with
``horovod_tpu``.

LiquidAI/LFM2-24B-A2B ``config.json`` (``model_type: lfm2_moe``) gives the
shapes. Every block is ``h = x + Op(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``
with eps 1e-5 and no bias anywhere; after the last block one more RMSNorm and
the head, which is the embedding's own rows.

``Op`` by ``layer_types[i]``. ``conv``: ``in_proj`` (d -> 3d) gives ``B``,
``C``, ``X`` (its first, second and third d channels); ``z = B * X``;
``c[t] = sum_{j < K} w[:, j] * z[t - (K - 1) + j]`` with ``z`` zero before
the row's start (depthwise, causal, K = ``conv_L_cache`` = 3, written here as
an explicit sum over the taps); ``out_proj(C * c)``. ``full_attention``: q
(32 heads of 64), k, v (8 heads of 64); q and k each through an RMSNorm over
the 64 of every head with its own gain; RoPE (rotate-half, theta 1e6);
``softmax(q k^T / 8 + causal mask) v``, each key/value head serving 4 query
heads in order; an output projection.

``FF``: in the first ``num_dense_layers`` blocks ``w2(silu(w1 u) * w3 u)`` at
the dense width; in the others ``s = sigmoid(W_r u)`` over all experts in
float32, the top 4 of ``s + b`` (``b`` the layer's selection bias, a buffer),
gates ``s`` of the chosen (unbiased), divided by their sum ``+ 1e-6``, times
``routed_scaling_factor``; ``sum_e g_e W_down,e (silu(W_gate,e u) * W_up,e
u)``. The loss is the mean next-token cross entropy over the ``T - 1``
positions of each row.

Departures, each the configuration's own (``configs/*.json`` states them):
the chip's share of an expert-parallel deployment: the sum runs over the
chosen experts that are held (``experts_first .. + held``) while the
normalisation stays over all chosen, and what the absent experts would add
is left out; a slice of the vocabulary is the whole vocabulary; the bias is
held fixed. No token is dropped: every position goes through every held
expert and is weighted by its (possibly zero) gate. Attention is computed one
query head at a time and the experts one at a time, each under
``jax.checkpoint``, and every block under one, so that a row of 8,192
positions fits. Matrix products run under
``default_matmul_precision("highest")``: on a TPU a float32 product is
otherwise rounded like bfloat16. ``dtype`` computes the whole forward in
another precision, ``router_dtype`` the router's logits alone and
``with_inputs`` hands back what every router chose from, for the readings
that set the limits (``controls_lfm2.py``); :func:`router_choices` is the
routing alone, again, on given inputs.
"""

import functools

import jax
import jax.numpy as jnp

STATIC = ("layer_types", "num_dense_layers", "num_heads", "num_kv_heads",
          "eps", "rope_theta", "top_k", "norm_topk", "routed_scale",
          "experts_first", "dtype", "router_dtype", "with_inputs")
NORM_EPS = 1e-6         # what the family adds to the sum of the chosen gates


@functools.partial(jax.jit, static_argnames=("num_layers",))
def from_system(params, num_layers):
    """The system's flax tree (``wte``, ``h<i>/...``, ``norm_f``) as the
    reference's: float32, the blocks in a list (they differ by layer)."""
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.asarray(x, jnp.float32), tree)
    return {"wte": f32(params["wte"]),
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


def _attention(u, p, *, num_heads, num_kv_heads, eps, rope_theta):
    """One row (T, d): every query head on its own, so that one (T, T) score
    matrix is alive at a time."""
    T = u.shape[0]
    hd = p["wq"]["kernel"].shape[1] // num_heads
    q = (u @ p["wq"]["kernel"]).reshape(T, num_heads, hd)
    k = (u @ p["wk"]["kernel"]).reshape(T, num_kv_heads, hd)
    v = (u @ p["wv"]["kernel"]).reshape(T, num_kv_heads, hd)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), rope_theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), rope_theta)
    group = num_heads // num_kv_heads
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    @jax.checkpoint
    def head(h):
        kv = h // group
        s = (q[:, h] @ k[:, kv].T) / jnp.sqrt(jnp.asarray(hd, u.dtype))
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v[:, kv]

    o = jax.lax.map(head, jnp.arange(num_heads))            # (H, T, hd)
    return o.transpose(1, 0, 2).reshape(T, num_heads * hd) @ p["wo"]["kernel"]


def _short_conv(u, p):
    """One row (T, d): the gates, and the convolution as the sum over its
    taps of the gated input shifted back by ``K - 1 - j`` positions."""
    T, d = u.shape
    bcx = u @ p["in_proj"]["kernel"]
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b * x
    K = p["taps"].shape[1]
    conv = jnp.zeros_like(z)
    for j in range(K):
        back = K - 1 - j
        conv = conv + p["taps"][:, j] * jnp.pad(z, ((back, 0), (0, 0)))[:T]
    return (c * conv) @ p["out_proj"]["kernel"]


def _dense(u, p):
    return ((jax.nn.silu(u @ p["w1"]["kernel"]) * (u @ p["w3"]["kernel"]))
            @ p["w2"]["kernel"])


def route(u, router, bias, *, top_k, norm_topk, routed_scale,
          router_dtype=None):
    """``(gate, choice)`` (T, top_k): a sigmoid score an expert (in float32,
    as everything here, unless ``dtype`` or ``router_dtype`` says
    otherwise); the top ``top_k`` of score plus ``bias`` (experts,), or of
    the scores alone where it is None; the gates are the unbiased scores of
    the chosen, over their sum ``+ 1e-6``, times ``routed_scale``."""
    if router_dtype is not None:        # the logits alone in that precision
        u, router = u.astype(router_dtype), router.astype(router_dtype)
    s = jax.nn.sigmoid((u @ router).astype(jnp.float32))
    _, choice = jax.lax.top_k(s if bias is None else s + bias, top_k)
    gate = jnp.take_along_axis(s, choice, axis=-1)
    if norm_topk:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + NORM_EPS)
    return gate * routed_scale, choice


def _experts(u, p, bias, *, top_k, norm_topk, routed_scale, experts_first,
             router_dtype=None):
    """The held experts' share for one row (T, d): a loop over the experts
    held; every position goes through each and is weighted by its gate for
    that expert, zero where it did not choose it."""
    gate, choice = route(u, p["router"], bias, top_k=top_k,
                         norm_topk=norm_topk, routed_scale=routed_scale,
                         router_dtype=router_dtype)
    held = p["w_gate"].shape[0]

    @jax.checkpoint
    def one(acc, xs):
        e, w_gate, w_up, w_down = xs
        w = jnp.sum(jnp.where(choice == experts_first + e, gate, 0.0),
                    axis=-1).astype(u.dtype)
        y = (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]))
    return out, (choice, u)


def _block(x, p, bias, kind, dense, kw):
    """One block on rows ``x`` (B, T, d). ``(y, routed)``: ``routed`` is None
    for a dense block, else the layer's choices (B, T, top_k), with
    ``with_inputs`` beside the router's inputs (B, T, d)."""
    eps = kw["eps"]
    if kind == "conv":
        op = lambda row: _short_conv(row, p["conv"])
    elif kind == "full_attention":
        attn = {k: kw[k] for k in ("num_heads", "num_kv_heads", "eps",
                                   "rope_theta")}
        op = lambda row: _attention(row, p["attn"], **attn)
    else:
        raise ValueError(f"layer type {kind!r}")
    h = x + jax.vmap(lambda row: op(_rms(row, p["norm_op"]["scale"], eps)))(x)
    norm = lambda row: _rms(row, p["norm_ff"]["scale"], eps)
    if dense:
        return h + jax.vmap(lambda row: _dense(norm(row), p["mlp"]))(h), None
    moe = {k: kw[k] for k in ("top_k", "norm_topk", "routed_scale",
                              "experts_first", "router_dtype")}
    y, (choice, u) = jax.vmap(lambda row: _experts(
        norm(row), p["moe"], bias, **moe))(h)
    return h + y, ((choice, u) if kw["with_inputs"] else choice)


def _layers(ref, tokens, expert_bias, kw):
    """``(hidden, routed, head)``: the rows after the final norm (B, T, d);
    every routed layer's choices stacked (Lr, B, T, top_k), with
    ``with_inputs`` a pair with the routers' inputs (Lr, B, T, d); the
    head's rows."""
    dtype = jnp.dtype(kw["dtype"])
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    x = ref["wte"][tokens]
    routed = []
    for i, (p, kind) in enumerate(zip(ref["h"], kw["layer_types"])):
        dense = i < kw["num_dense_layers"]
        bias = None if dense or expert_bias is None else expert_bias[i]
        block = jax.checkpoint(functools.partial(
            _block, kind=kind, dense=dense, kw=kw))
        x, kept = block(x, p, bias)
        if not dense:
            routed.append(kept)
    routed = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *routed)
    return _rms(x, ref["norm_f"], kw["eps"]), routed, ref["wte"]


def _defaults(kw):
    kw = dict(kw)
    kw.setdefault("dtype", "float32")
    kw.setdefault("router_dtype", None)
    kw.setdefault("with_inputs", False)
    return kw


def loss(ref, tokens, expert_bias, **kw):
    """Mean next-token cross entropy of ``tokens`` (B, T) over the ``T - 1``
    positions of each row, through the tied head."""
    kw = _defaults(kw)
    with jax.default_matmul_precision("highest"):
        hidden, _, head = _layers(ref, tokens, expert_bias, kw)
        logp = jax.nn.log_softmax(hidden[:, :-1] @ head.T, axis=-1)
        ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(ll.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=STATIC)
def choices(ref, tokens, expert_bias, **kw):
    """Every routed layer's choices, (Lr, B, T, top_k): which experts each
    position chose, held here or not. ``with_inputs``: ``(choices,
    inputs)``, the router's inputs (Lr, B, T, d) beside them."""
    kw = _defaults(kw)
    with jax.default_matmul_precision("highest"):
        return _layers(ref, tokens, expert_bias, kw)[1]


@functools.partial(jax.jit, static_argnames=("top_k", "router_dtype"))
def router_choices(inputs, routers, biases, *, top_k, router_dtype=None):
    """The routing alone, again, on given inputs: ``inputs`` (Lr, ..., d) in
    whatever precision they were computed, ``routers`` (Lr, d, experts),
    ``biases`` (Lr, experts) or None for the top of the unbiased scores; the
    choices (Lr, ..., top_k) of a float32 router on exactly these inputs.
    What a side's own choices are held to when the question is the router's
    precision, or its bias, and not what came before it."""
    def layer(xs):
        u, router, bias = xs
        return route(u.astype(jnp.float32), router, bias, top_k=top_k,
                     norm_topk=False, routed_scale=1.0,
                     router_dtype=router_dtype)[1]
    if biases is None:
        biases = jnp.zeros((routers.shape[0], routers.shape[-1]),
                           jnp.float32)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(layer, (inputs, routers, biases))


@functools.partial(jax.jit, static_argnames=STATIC, donate_argnums=(1, 2))
def ref_microbatch(ref, loss_sum, grad_sum, tokens, expert_bias, **kw):
    """Add one micro-batch's loss and gradients to the running sums."""
    value, grads = jax.value_and_grad(loss)(ref, tokens, expert_bias, **kw)
    return loss_sum + value, jax.tree_util.tree_map(jnp.add, grad_sum, grads)


@jax.jit
def ref_norm(grad_sum, n):
    return jnp.sqrt(sum(jnp.sum((g / n) ** 2) for g in
                        jax.tree_util.tree_leaves(grad_sum)))


def loss_and_grad_norm(ref, tokens, expert_bias, *, micro=1, **kw):
    """Loss of the whole batch and the norm of its gradient, taken in
    micro-batches of ``micro`` rows (equal sizes, so the batch mean is the
    mean of the micro-batch means)."""
    if tokens.shape[0] % micro:
        raise ValueError(f"batch {tokens.shape[0]} is no multiple of {micro}")
    n = tokens.shape[0] // micro
    loss_sum = jnp.zeros((), jnp.float32)
    grad_sum = jax.tree_util.tree_map(jnp.zeros_like, ref)
    for i in range(n):
        loss_sum, grad_sum = ref_microbatch(
            ref, loss_sum, grad_sum, tokens[i * micro:(i + 1) * micro],
            expert_bias, **kw)
    return float(loss_sum) / n, float(ref_norm(grad_sum, jnp.float32(n)))
