"""The GLM-4-MoE-Lite decoder (latent attention, routed experts beside a
shared one, a multi-token-prediction module), as published, in plain
``jax.numpy`` and float32: forward, the loss (both terms) and its gradients.
The yardstick the system's outputs are held to; it shares no code with
``horovod_tpu``.

zai-org/GLM-4.7-Flash ``config.json`` (``model_type: glm4_moe_lite``) gives
the shapes; every one of its keys is DeepSeek-V3's, whose equations
(DeepSeek-V2 section 2.1, DeepSeek-V3 sections 2.1 and 2.2) these are. Every
block is ``h = x + Attn(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))`` with eps
1e-5 and no bias anywhere; after the last block one more RMSNorm and the
untied head.

``Attn(u)``, one row (T, d), H heads. ``c_q = RMSNorm(u W_qa)``
(``q_lora_rank``); ``q = c_q W_qb``, a head of ``nope + rope`` columns, its
first ``nope`` without position and its last ``rope`` rotated. ``[c_kv ;
k_r] = u W_kva`` (``kv_lora_rank + rope``); ``c_kv <- RMSNorm(c_kv)``;
``k_rope = RoPE(k_r)``: **one key of ``rope`` columns a token, the same for
every head**. ``[k_nope ; v] = c_kv W_kvb``, a head of ``nope + v_head``
columns. Head ``j``'s scores are ``(q_nope_j k_nope_j^T + RoPE(q_rope_j)
k_rope^T) / sqrt(nope + rope)`` under the causal mask: the shared key is
used by hand and never copied; ``o_j = softmax(scores) v_j``; the heads
side by side through ``W_o``. RoPE is rotate-half with theta 1e6 over the
``rope`` columns.

``FF``: in the first ``num_dense_layers`` blocks a SwiGLU at the dense
width; in the others ``s = sigmoid(W_r u)`` over all experts in float32,
the top 4 of ``s + b`` (``b`` the layer's selection bias, a buffer; with one
group the family's group mask is all ones), gates ``s`` of the chosen
(unbiased) over their sum ``+ 1e-20``, times ``routed_scaling_factor``;
``sum_e g_e W_down,e (silu(W_gate,e u) * W_up,e u)`` **plus the shared
expert**, one more SwiGLU of every position, unweighted.

The multi-token-prediction module, depth 1, on one row: for ``t = 0 .. T -
2``, ``m_t = [RMSNorm_e(Emb(token_{t+1})) ; RMSNorm_h(z_t)] W_eh`` with
``z`` the trunk's output after its final norm and ``Emb`` the trunk's own
embedding; ``r = Block(m)``, a block of the routed kind over those ``T - 1``
positions; ``Head(RMSNorm_s(r_t))`` through the trunk's own head predicts
``token_{t+2}``, scored for ``t = 0 .. T - 3``. The loss is ``CE_main +
mtp_weight * CE_mtp``, each a mean over the positions of each row that have
a target.

Departures, each the configuration's own (``configs/*.json`` states them):
the chip's share of an expert-parallel deployment: the sum runs over the
chosen experts that are held (``experts_first .. + held``) while the
normalisation stays over all chosen, what the absent experts would add is
left out, and the shared expert is whole; a slice of the vocabulary is the
whole vocabulary; the bias is held fixed. No token is dropped. Attention is
computed one head at a time and the experts one at a time, each under
``jax.checkpoint``, and every block under one, so that a row of 8,192
positions fits. Matrix products run under
``default_matmul_precision("highest")``. ``dtype`` computes the whole
forward in another precision, ``router_dtype`` the router's logits alone,
``shared=False`` leaves the shared expert out and ``mtp_weight=0`` the
module's term, for the readings that set the limits (``controls_glm4.py``).
"""

import functools

import jax
import jax.numpy as jnp

# what is the same arithmetic letter for letter, and no part of what is new
# here: the norm, rotate-half RoPE over (T, H, D), the routing alone on given
# inputs (top-k of sigmoid scores plus a bias), the norm of summed gradients
from reference.lfm2_moe_ref import _rms, _rope, ref_norm, router_choices

__all__ = ["from_system", "loss", "loss_terms", "choices", "router_choices",
           "loss_and_grad_norm"]

STATIC = ("num_dense_layers", "num_heads", "qk_nope_head_dim", "eps",
          "rope_theta", "top_k", "norm_topk", "routed_scale",
          "experts_first", "mtp_weight", "dtype", "router_dtype", "shared")
NORM_EPS = 1e-20        # what the family adds to the sum of the chosen gates


@functools.partial(jax.jit, static_argnames=("num_layers",))
def from_system(params, num_layers):
    """The system's flax tree (``wte``, ``lm_head``, ``h<i>/...``,
    ``norm_f``, ``mtp/...``) as the reference's: float32, the blocks in a
    list (they differ by layer), the module's parts beside its block."""
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.asarray(x, jnp.float32), tree)
    ref = {"wte": f32(params["wte"]), "lm_head": f32(params["lm_head"]),
           "norm_f": f32(params["norm_f"]["scale"]),
           "h": [f32(params[f"h{i}"]) for i in range(num_layers)]}
    if "mtp" in params:
        ref["mtp"] = f32(params["mtp"])
    return ref


# --------------------------------------------------------------------------
# the layer
# --------------------------------------------------------------------------

def _attention(u, p, *, num_heads, qk_nope_head_dim, eps, rope_theta):
    """One row (T, d): every head on its own, so that one (T, T) score
    matrix is alive at a time, and the one rotated key used for each."""
    T, H, nope = u.shape[0], num_heads, qk_nope_head_dim
    c_q = _rms(u @ p["q_a"]["kernel"], p["q_norm"]["scale"], eps)
    q = (c_q @ p["q_b"]["kernel"]).reshape(T, H, -1)
    rank = p["kv_norm"]["scale"].shape[0]
    kv = u @ p["kv_a"]["kernel"]
    c_kv = _rms(kv[:, :rank], p["kv_norm"]["scale"], eps)
    k_rope = _rope(kv[:, None, rank:], rope_theta)[:, 0]         # (T, rope)
    kv_up = (c_kv @ p["kv_b"]["kernel"]).reshape(T, H, -1)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], rope_theta)
    k_nope, v = kv_up[..., :nope], kv_up[..., nope:]
    width = jnp.asarray(q.shape[-1], u.dtype)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    @jax.checkpoint
    def head(h):
        s = (q_nope[:, h] @ k_nope[:, h].T + q_rope[:, h] @ k_rope.T
             ) / jnp.sqrt(width)
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v[:, h]

    o = jax.lax.map(head, jnp.arange(H))                    # (H, T, v_head)
    return o.transpose(1, 0, 2).reshape(T, -1) @ p["o"]["kernel"]


def _swiglu(u, p):
    return ((jax.nn.silu(u @ p["w_gate"]["kernel"])
             * (u @ p["w_up"]["kernel"])) @ p["w_down"]["kernel"])


def route(u, router, bias, *, top_k, norm_topk, routed_scale,
          router_dtype=None):
    """``(gate, choice)`` (T, top_k): a sigmoid score an expert (in float32,
    as everything here, unless ``dtype`` or ``router_dtype`` says
    otherwise); the top ``top_k`` of score plus ``bias`` (experts,), or of
    the scores alone where it is None; the gates are the unbiased scores of
    the chosen, over their sum ``+ 1e-20``, times ``routed_scale``."""
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
    """The held routed experts' share for one row (T, d): a loop over the
    experts held; every position goes through each and is weighted by its
    gate for that expert, zero where it did not choose it."""
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
    return out, choice


def _block(x, p, bias, dense, kw):
    """One block on rows ``x`` (B, T, d). ``(y, routed)``: ``routed`` is None
    for a dense block, else the layer's choices (B, T, top_k)."""
    eps = kw["eps"]
    attn = {k: kw[k] for k in ("num_heads", "qk_nope_head_dim", "eps",
                               "rope_theta")}
    h = x + jax.vmap(lambda row: _attention(
        _rms(row, p["norm_in"]["scale"], eps), p["attn"], **attn))(x)
    norm = lambda row: _rms(row, p["norm_post"]["scale"], eps)
    if dense:
        return h + jax.vmap(lambda row: _swiglu(norm(row), p["mlp"]))(h), None
    moe = {k: kw[k] for k in ("top_k", "norm_topk", "routed_scale",
                              "experts_first", "router_dtype")}

    def sparse(row):
        u = norm(row)
        y, kept = _experts(u, p["moe"], bias, **moe)
        if kw["shared"]:
            y = y + _swiglu(u, p["shared"])         # every position, once
        return y, kept

    y, choice = jax.vmap(sparse)(h)
    return h + y, choice


def _forward(ref, tokens, expert_bias, kw):
    """``(hidden, ahead, routed, routed_mtp, head)``: the trunk's rows after
    the final norm (B, T, d); the module's after its last norm (B, T - 1,
    d), or None; every routed trunk layer's choices stacked (Lr, B, T,
    top_k) and the module's (B, T - 1, top_k); the head's rows."""
    dtype = jnp.dtype(kw["dtype"])
    ref = jax.tree_util.tree_map(lambda a: a.astype(dtype), ref)
    row = lambda i: None if expert_bias is None else expert_bias[i]
    x = ref["wte"][tokens]
    routed = []
    for i, p in enumerate(ref["h"]):
        dense = i < kw["num_dense_layers"]
        x, kept = jax.checkpoint(functools.partial(
            _block, dense=dense, kw=kw))(x, p, None if dense else row(i))
        if not dense:
            routed.append(kept)
    routed = jnp.stack(routed)
    hidden = _rms(x, ref["norm_f"], kw["eps"])
    ahead = routed_mtp = None
    if "mtp" in ref and kw["mtp_weight"]:
        p, eps = ref["mtp"], kw["eps"]
        m = jnp.concatenate(
            [_rms(ref["wte"][tokens[:, 1:]], p["norm_e"]["scale"], eps),
             _rms(hidden[:, :-1], p["norm_h"]["scale"], eps)], axis=-1
        ) @ p["eh_proj"]["kernel"]
        r, routed_mtp = jax.checkpoint(functools.partial(
            _block, dense=False, kw=kw))(m, p["block"], row(len(ref["h"])))
        ahead = _rms(r, p["norm_s"]["scale"], eps)
    return hidden, ahead, routed, routed_mtp, ref["lm_head"]


def _defaults(kw):
    kw = dict(kw)
    kw.setdefault("mtp_weight", 0.1)
    kw.setdefault("dtype", "float32")
    kw.setdefault("router_dtype", None)
    kw.setdefault("shared", True)
    return kw


def _cross_entropy(hidden, head, targets):
    logp = jax.nn.log_softmax(hidden @ head.T, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll.astype(jnp.float32))


def loss_terms(ref, tokens, expert_bias, **kw):
    """``(CE_main, CE_mtp)`` of ``tokens`` (B, T): the next token's mean
    cross entropy over the ``T - 1`` positions of each row, and the
    module's, of the token two ahead over ``T - 2`` (0 where the tree has
    no module or ``mtp_weight`` is 0)."""
    kw = _defaults(kw)
    with jax.default_matmul_precision("highest"):
        hidden, ahead, _, _, head = _forward(ref, tokens, expert_bias, kw)
        main = _cross_entropy(hidden[:, :-1], head, tokens[:, 1:])
        if ahead is None:
            return main, jnp.zeros((), jnp.float32)
        return main, _cross_entropy(ahead[:, :-1], head, tokens[:, 2:])


def loss(ref, tokens, expert_bias, **kw):
    """``CE_main + mtp_weight * CE_mtp`` (:func:`loss_terms`)."""
    main, mtp = loss_terms(ref, tokens, expert_bias, **kw)
    return main + _defaults(kw)["mtp_weight"] * mtp


@functools.partial(jax.jit, static_argnames=STATIC)
def choices(ref, tokens, expert_bias, **kw):
    """``(trunk, module)``: every routed trunk layer's choices (Lr, B, T,
    top_k) and the module's block's (B, T - 1, top_k), or None without the
    module: which experts each position chose, held here or not."""
    kw = _defaults(kw)
    with jax.default_matmul_precision("highest"):
        return _forward(ref, tokens, expert_bias, kw)[2:4]


@functools.partial(jax.jit, static_argnames=STATIC, donate_argnums=(1, 2))
def ref_microbatch(ref, loss_sum, grad_sum, tokens, expert_bias, **kw):
    """Add one micro-batch's loss and gradients to the running sums."""
    value, grads = jax.value_and_grad(loss)(ref, tokens, expert_bias, **kw)
    return loss_sum + value, jax.tree_util.tree_map(jnp.add, grad_sum, grads)


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
