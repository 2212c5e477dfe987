"""The SDAR block-diffusion decoder with routed experts, as published, in
plain ``jax.numpy`` and float32: forward, the masked-diffusion loss and its
gradients. The yardstick the system's outputs are held to; it shares no
code with ``horovod_tpu``.

JetLM/SDAR-30B-A3B-Chat ``config.json`` (``model_type: sdar_moe``) gives the
shapes; the layer is a Qwen3-MoE-shaped block: ``h = x + Attn(RMSNorm(x))``,
``y = h + MoE(RMSNorm(h))``, no bias anywhere, eps 1e-6, a final RMSNorm and
an untied head. Attn: q (32 heads of 128), k, v (4 heads of 128); q and k
each through an RMSNorm over the 128 of every head with its own gain; RoPE
(rotate-half) at the position id; ``softmax(q k^T / sqrt(128) + mask) v``,
each key/value head serving 8 query heads in order; an output projection.
MoE: ``r = softmax(W_r u)`` over all experts in float32, the top 8, weights
renormalised over the chosen ones, ``sum_e w_e W_down,e (silu(W_gate,e u) *
W_up,e u)``. Training is masked diffusion over blocks: every block of
``block_len`` tokens draws a level ``t`` in ``[t_min, 1]``, each of its
tokens is replaced by the mask id with probability ``t``, the row runs as
``[noisy ; clean]`` (2T positions, position ids ``0..T-1, 0..T-1``) under a
dense mask (:func:`visible`), only the noisy half goes through the head, and
the loss is the sum over masked positions of ``CE / t`` over ``rows x T``.

Departures, each the configuration's own (``configs/*.json`` states them):
the chip's share of an expert-parallel deployment — the sum runs over the
chosen experts that are held (``experts_first .. + held``) while the
normalisation stays over all chosen, and what the absent experts would add
is left out; a slice of the vocabulary is the whole vocabulary; no auxiliary
loss (the published config gives no coefficient). No token is dropped: every
position goes through every held expert and is weighted by its (possibly
zero) gate. Attention is computed one query head at a time and the experts
one at a time, each under ``jax.checkpoint``, so that a row of 8,192
positions fits; the layers are scanned so that the program holds one. Matrix
products run under ``default_matmul_precision("highest")``: on a TPU a
float32 product is otherwise rounded like bfloat16. ``dtype`` computes the
whole forward in another precision, ``router_dtype`` the router's logits
alone and ``with_inputs`` hands back what every router chose from, for the
readings that set the limits (``controls_sdar.py``);
:func:`router_choices` is the routing alone, again, on given inputs.

The noise is data derived from the tokens (:func:`row_keys`): a row's levels
and mask follow from a key folded from the row's own tokens, so that the
timed step, the check step and this file's micro-batches see the same noise.
"""

import functools

import jax
import jax.numpy as jnp

STATIC = ("num_heads", "num_kv_heads", "eps", "rope_theta", "top_k",
          "norm_topk", "experts_first", "block_len", "t_min", "mask_id",
          "dtype", "router_dtype", "with_inputs")


@functools.partial(jax.jit, static_argnames=("num_layers",))
def from_system(params, num_layers):
    """The system's flax tree (``wte``, ``lm_head``, ``h<i>/...``,
    ``norm_f``) as the reference's: float32, the blocks stacked on a leading
    axis."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    blocks = [params[f"h{i}"] for i in range(num_layers)]
    return {
        "wte": f32(params["wte"]), "lm_head": f32(params["lm_head"]),
        "norm_f": f32(params["norm_f"]["scale"]),
        "h": jax.tree_util.tree_map(
            lambda *xs: jnp.stack([f32(x) for x in xs]), *blocks),
    }


# --------------------------------------------------------------------------
# the noise, from the tokens
# --------------------------------------------------------------------------

def row_keys(tokens):
    """One PRNG key a row, folded from the row's own tokens."""
    t = tokens.astype(jnp.uint32)
    mult = (jnp.arange(tokens.shape[1], dtype=jnp.uint32)
            * jnp.uint32(2654435761) + jnp.uint32(40503))
    digest = jnp.sum(t * mult[None, :], axis=1, dtype=jnp.uint32)
    return jax.vmap(lambda d: jax.random.fold_in(jax.random.PRNGKey(27), d)
                    )(digest)


def noise(tokens, block_len, t_min):
    """``(levels, masked)`` of every row: levels (B, T / block_len) uniform
    in ``[t_min, 1]``; masked (B, T) bool, each token with the probability
    its block's level gives."""
    T = tokens.shape[1]

    def one(key):
        k_level, k_mask = jax.random.split(key)
        levels = jax.random.uniform(k_level, (T // block_len,), jnp.float32,
                                    t_min, 1.0)
        u = jax.random.uniform(k_mask, (T,), jnp.float32)
        return levels, u < jnp.repeat(levels, block_len)
    return jax.vmap(one)(row_keys(tokens))


def visible(T, block_len):
    """The dense block-diffusion mask over ``[noisy ; clean]``, (2T, 2T)
    bool: query i sees key j iff both are noisy and share a block, or i is
    noisy, j clean and j's block lies before i's, or both are clean and j's
    block is not after i's. A clean query never sees a noisy key."""
    pos = jnp.arange(2 * T)
    noisy = pos < T
    blk = (pos % T) // block_len
    qn, kn = noisy[:, None], noisy[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


# --------------------------------------------------------------------------
# the layer
# --------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half RoPE over (S, H, D) at position ids ``pos`` (S,)."""
    d2 = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(u, p, pos, mask, *, num_heads, num_kv_heads, eps, rope_theta):
    """One row (S, d): every query head on its own, so that one (S, S) score
    matrix is alive at a time."""
    S = u.shape[0]
    hd = p["wq"]["kernel"].shape[1] // num_heads
    q = (u @ p["wq"]["kernel"]).reshape(S, num_heads, hd)
    k = (u @ p["wk"]["kernel"]).reshape(S, num_kv_heads, hd)
    v = (u @ p["wv"]["kernel"]).reshape(S, num_kv_heads, hd)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), pos, rope_theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), pos, rope_theta)
    group = num_heads // num_kv_heads

    @jax.checkpoint
    def head(h):
        kv = h // group
        s = (q[:, h] @ k[:, kv].T) / jnp.sqrt(jnp.asarray(hd, u.dtype))
        s = jnp.where(mask, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v[:, kv]

    o = jax.lax.map(head, jnp.arange(num_heads))            # (H, S, hd)
    return o.transpose(1, 0, 2).reshape(S, num_heads * hd) @ p["wo"]["kernel"]


def route(u, router, *, top_k, norm_topk, router_dtype=None):
    """``(gate, choice)`` (S, top_k): softmax over all experts (in float32,
    as everything here, unless ``dtype`` or ``router_dtype`` says
    otherwise), the top ``top_k``, renormalised over the chosen ones."""
    if router_dtype is not None:        # the logits alone in that precision
        u, router = u.astype(router_dtype), router.astype(router_dtype)
    r = jax.nn.softmax((u @ router).astype(jnp.float32), axis=-1)
    gate, choice = jax.lax.top_k(r, top_k)
    if norm_topk:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return gate, choice


def _experts(u, p, *, top_k, norm_topk, experts_first, router_dtype=None):
    """The held experts' share for one row (S, d): a loop over the experts
    held; every position goes through each and is weighted by its gate for
    that expert, zero where it did not choose it."""
    gate, choice = route(u, p["router"], top_k=top_k, norm_topk=norm_topk,
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


def _block(x, p, pos, mask, kw):
    attn = {k: kw[k] for k in ("num_heads", "num_kv_heads", "eps",
                               "rope_theta")}
    moe = {k: kw[k] for k in ("top_k", "norm_topk", "experts_first",
                              "router_dtype")}
    eps = kw["eps"]
    h = x + jax.vmap(lambda row: _attention(
        _rms(row, p["norm_attn"]["scale"], eps), p["attn"], pos, mask,
        **attn))(x)
    y, (choice, u) = jax.vmap(lambda row: _experts(
        _rms(row, p["norm_mlp"]["scale"], eps), p["moe"], **moe))(h)
    return h + y, ((choice, u) if kw["with_inputs"] else choice)


def _layers(ref, noisy, clean, kw):
    """``(hidden, choices, head)``: the noisy half after the final norm (B,
    T, d), and every layer's routing choices (L, B, 2T, top_k); with
    ``with_inputs`` each layer's router input (L, B, 2T, d) beside them."""
    dtype = jnp.dtype(kw["dtype"])
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    T = clean.shape[1]
    pos = jnp.concatenate([jnp.arange(T), jnp.arange(T)])
    mask = visible(T, kw["block_len"])
    x = ref["wte"][jnp.concatenate([noisy, clean], axis=1)]
    block = jax.checkpoint(lambda x, p: _block(x, p, pos, mask, kw))
    x, choices = jax.lax.scan(block, x, ref["h"])
    return _rms(x[:, :T], ref["norm_f"], kw["eps"]), choices, ref["lm_head"]


def _defaults(kw):
    kw = dict(kw)
    kw.setdefault("dtype", "float32")
    kw.setdefault("router_dtype", None)
    kw.setdefault("with_inputs", False)
    return kw


def loss(ref, tokens, **kw):
    """The masked-diffusion loss of ``tokens`` (B, T) under the noise that
    follows from them: sum over masked positions of ``CE / t`` over
    ``B * T``."""
    kw = _defaults(kw)
    with jax.default_matmul_precision("highest"):
        levels, masked = noise(tokens, kw["block_len"], kw["t_min"])
        noisy = jnp.where(masked, kw["mask_id"], tokens)
        hidden, _, head = _layers(ref, noisy, tokens, kw)
        logp = jax.nn.log_softmax(hidden @ head.T, axis=-1)
        ll = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        weight = masked / jnp.repeat(levels, kw["block_len"], axis=1)
        return -jnp.sum(ll.astype(jnp.float32) * weight) / tokens.size


@functools.partial(jax.jit, static_argnames=STATIC)
def choices(ref, tokens, **kw):
    """Every layer's routing choices on the noised rows, (L, B, 2T, top_k):
    which experts each position chose, held here or not. ``with_inputs``:
    ``(choices, inputs)``, the router's inputs (L, B, 2T, d) beside them."""
    kw = _defaults(kw)
    with jax.default_matmul_precision("highest"):
        _, masked = noise(tokens, kw["block_len"], kw["t_min"])
        noisy = jnp.where(masked, kw["mask_id"], tokens)
        return _layers(ref, noisy, tokens, kw)[1]


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk",
                                             "router_dtype"))
def router_choices(inputs, routers, *, top_k, norm_topk, router_dtype=None):
    """The routing alone, again, on given inputs: ``inputs`` (L, ..., d) in
    whatever precision they were computed, ``routers`` (L, d, experts);
    the choices (L, ..., top_k) of a float32 router on exactly these
    inputs. What a side's own choices are held to when the question is the
    router's precision and not that of what came before it."""
    def layer(xs):
        u, router = xs
        return route(u.astype(jnp.float32), router, top_k=top_k,
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


def loss_and_grad_norm(ref, tokens, *, micro=1, **kw):
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
            ref, loss_sum, grad_sum, tokens[i * micro:(i + 1) * micro], **kw)
    return float(loss_sum) / n, float(ref_norm(grad_sum, jnp.float32(n)))
