"""Autoregressive generation with a KV cache for the decoder families.

Training forwards run the flash/sequence-parallel machinery; decode is a
different program — one token per step against cached K/V, static
shapes, the whole loop inside ONE ``lax.scan`` so XLA compiles a single
program with no per-token dispatch. This module implements that decode
program directly over the zoo's parameter trees (GPT-2 and Llama,
selected by the module type) rather than threading a ``decode`` flag
through the training modules: the two paths want different code, and the
parity tests pin them together — decode logits equal the training
forward position-by-position, and greedy generation matches
HuggingFace's ``generate`` on converted checkpoints
(``tests/test_generate.py``).

The cache is a plain pytree of ``(B, T_total, H, hd)`` arrays (one K and
one V per layer), donated through the scan carry. Sampling: greedy at
``temperature=0`` (the default), otherwise temperature softmax with
optional top-k truncation; an ``eos_id`` freezes finished rows.

Decode steps compute in the model's ``cfg.dtype`` with the SAME fp32
islands as the training forward (fp32 norms and softmax, fp32 logits
head): the per-layer cast back to bf16 re-synchronizes the two lowerings
at every boundary, which is what makes greedy decode-vs-forward parity
hold bit-for-bit instead of drifting by reduction-order noise. The
residual near-ties are closed by :func:`greedy_token`'s deterministic
tolerance tie-break.

The per-family step functions and cache allocators are exposed through a
registry (:func:`decode_step` / :func:`init_cache` / :func:`decode_family`)
shared by :func:`generate` here and the continuous-batching serving engine
(``horovod_tpu.serving``): one decode program, two drivers. Steps accept
either the plain dense cache dict (scalar position — the ``generate()``
scan) or any object implementing the small KV-cache protocol
(``update(layer, k, v, pos) -> (cache, ck, cv)``) with per-row ``(B,)``
positions — what the serving engine's paged cache plugs in.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["generate", "t5_generate", "greedy_token",
           "decode_step", "decode_verify_step", "init_cache",
           "decode_family", "DecodeFamily",
           "DenseKVCache", "t5_decoder_bias", "t5_encode"]


def _layernorm(x, p, eps):
    """Mirrors ``flax.linen.LayerNorm(dtype=float32)`` bit for bit: fp32
    fast-variance stats (``E[x^2] - E[x]^2``) and the scale folded into
    the rsqrt multiplier BEFORE it touches x — the association the
    training forward compiled. Returns fp32."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = (xf * xf).mean(-1, keepdims=True) - mu * mu
    mul = jax.lax.rsqrt(var + eps) * p["scale"]
    return (xf - mu) * mul + p["bias"]


def _rmsnorm(x, p, eps):
    """Training ``RMSNorm`` (llama.py, shared by t5): fp32 inside, cast
    back to the residual dtype — the cast is load-bearing for parity."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * p["scale"]).astype(x.dtype)


class DenseKVCache:
    """The plain dense cache as a protocol object: a pytree over the
    ``{layer: {"k","v"}}`` dict :func:`init_cache` allocates. ``update``
    keeps the scalar-position path on ``dynamic_update_index_in_dim``
    (what ``generate()``'s scan compiled since PR 3 — a dynamic-update-
    slice XLA aliases in place) and uses a per-row scatter only for
    ``(B,)`` vector positions."""

    __slots__ = ("layers",)

    def __init__(self, layers):
        self.layers = layers

    def update(self, layer, k, v, pos):
        ent = self.layers[layer]
        if jnp.ndim(pos) == 0:
            ck = jax.lax.dynamic_update_index_in_dim(ent["k"], k, pos,
                                                     axis=1)
            cv = jax.lax.dynamic_update_index_in_dim(ent["v"], v, pos,
                                                     axis=1)
        else:
            rows = jnp.arange(k.shape[0])
            ck = ent["k"].at[rows, pos].set(k)
            cv = ent["v"].at[rows, pos].set(v)
        layers = dict(self.layers)
        layers[layer] = {"k": ck, "v": cv}
        return DenseKVCache(layers), ck, cv

    def tree_flatten(self):
        return (self.layers,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])


jax.tree_util.register_pytree_node_class(DenseKVCache)


def _as_cache(cache):
    """Accept the raw dense dict (the public scan-carry format) or any
    protocol object; remember which so the step returns the same kind."""
    if isinstance(cache, dict):
        return DenseKVCache(cache), True
    return cache, False


def _key_mask(t, pos, lead_dims):
    """(..., t) bool: key position <= query position. ``pos`` scalar
    broadcasts everywhere; ``(B,)`` positions get ``lead_dims`` singleton
    axes between batch and keys (per-slot masks for the serving engine's
    mixed-progress lanes)."""
    ar = jnp.arange(t)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return ar <= pos
    return ar[(None,) * (lead_dims + 1)] <= \
        pos[(slice(None),) + (None,) * (lead_dims + 1)]


def _attend_cached(q, ck, cv, idx, scale):
    """One query (B, H, hd) over a cache (B, T, Hkv, hd), keys <= idx
    (``idx`` scalar, or ``(B,)`` per-row positions).

    GQA stays grouped end-to-end: the cache is stored at Hkv width (the
    whole point of grouped heads — H/Hkv times less KV memory) and the
    query heads fold into (Hkv, H/Hkv) groups for the score einsums
    instead of repeat-expanding K/V. Dtype flow mirrors the training
    dense path (``ops/attention.multihead_attention``): scores in the
    compute dtype then cast fp32, softmax fp32, probabilities cast back
    before the value einsum."""
    b, h, hd = q.shape
    hkv = ck.shape[2]
    qg = q.reshape(b, hkv, h // hkv, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, ck).astype(jnp.float32) * scale
    t = ck.shape[1]
    s = jnp.where(_key_mask(t, idx, 2), s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgt,btkd->bkgd", p, cv)
    return o.reshape(b, h, hd)


def _gpt2_step(cfg, params, cache, tok, idx):
    """tok (B,) at position idx -> (new_cache, logits (B, V)).

    ``idx`` is a scalar (all rows at one position — the ``generate()``
    scan) or ``(B,)`` per-row positions (the serving engine's lanes);
    ``cache`` is the dense dict or any KV-cache protocol object."""
    cache, raw = _as_cache(cache)
    dt = cfg.dtype
    H, hd = cfg.num_heads, cfg.d_model // cfg.num_heads
    x = params["wte"][tok].astype(dt) + params["wpe"][idx].astype(dt)
    for i in range(cfg.num_layers):
        p = params[f"h{i}"]
        h = _layernorm(x, p["ln1"], cfg.ln_eps).astype(dt)
        qkv = h @ p["attn"]["qkv"]["kernel"].astype(dt) \
            + p["attn"]["qkv"]["bias"].astype(dt)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        cache, ck, cv = cache.update(i, k.reshape(-1, H, hd),
                                     v.reshape(-1, H, hd), idx)
        o = _attend_cached(q.reshape(-1, H, hd), ck, cv, idx, hd ** -0.5)
        x = x + (o.reshape(-1, H * hd) @ p["attn"]["out"]["kernel"]
                 .astype(dt) + p["attn"]["out"]["bias"].astype(dt))
        h = _layernorm(x, p["ln2"], cfg.ln_eps).astype(dt)
        h = jax.nn.gelu(h @ p["mlp"]["fc"]["kernel"].astype(dt)
                        + p["mlp"]["fc"]["bias"].astype(dt))
        x = x + (h @ p["mlp"]["proj"]["kernel"].astype(dt)
                 + p["mlp"]["proj"]["bias"].astype(dt))
    x = _layernorm(x, params["ln_f"], cfg.ln_eps)        # fp32
    return (cache.layers if raw else cache), \
        x @ params["wte"].T                              # tied head, fp32


def _rope_one(x, pos, theta):
    """RoPE for a single position per row: x (B, H, hd) — THE training
    rotation (``models.llama.apply_rope``) on a length-1 sequence, so
    decode can never drift from the training convention. Scalar ``pos``
    rotates every row alike; ``(B,)`` rotates per row (serving lanes)."""
    from horovod_tpu.models.llama import apply_rope
    pos = jnp.asarray(pos)
    pos = pos[:, None] if pos.ndim else pos[None]
    return apply_rope(x[:, None], pos, theta)[:, 0]


def _llama_step(cfg, params, cache, tok, idx):
    cache, raw = _as_cache(cache)
    dt = cfg.dtype
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    hd = cfg.d_model // H
    x = params["wte"][tok].astype(dt)                    # (B, D)
    for i in range(cfg.num_layers):
        p = params[f"h{i}"]
        h = _rmsnorm(x, p["norm_attn"], cfg.rms_eps)
        q = (h @ p["attn"]["wq"]["kernel"].astype(dt)).reshape(-1, H, hd)
        k = (h @ p["attn"]["wk"]["kernel"].astype(dt)).reshape(-1, Hkv, hd)
        v = (h @ p["attn"]["wv"]["kernel"].astype(dt)).reshape(-1, Hkv, hd)
        q = _rope_one(q, idx, cfg.rope_theta)
        k = _rope_one(k, idx, cfg.rope_theta)
        cache, ck, cv = cache.update(i, k, v, idx)
        o = _attend_cached(q, ck, cv, idx, hd ** -0.5)
        x = x + o.reshape(-1, H * hd) @ p["attn"]["wo"]["kernel"].astype(dt)
        h = _rmsnorm(x, p["norm_mlp"], cfg.rms_eps)
        g = jax.nn.silu(h @ p["mlp"]["gate"]["kernel"].astype(dt))
        u = h @ p["mlp"]["up"]["kernel"].astype(dt)
        x = x + (g * u) @ p["mlp"]["down"]["kernel"].astype(dt)
    x = _rmsnorm(x, params["norm_f"], cfg.rms_eps)
    return (cache.layers if raw else cache), \
        x.astype(jnp.float32) @ params["lm_head"].T      # untied head


def _t5_encode(model, cfg, params, src, src_mask):
    """Encoder states (THE training encoder — ``T5.__call__`` with
    ``dec_tokens=None``, shared attention dispatch and all) + per-layer
    cross-attention K/V, computed ONCE per generation. Stays in
    ``cfg.dtype`` end to end, exactly like the training decoder's view
    of the encoder output."""
    H, hd = cfg.num_heads, cfg.head_dim
    T = src.shape[1]
    dt = cfg.dtype
    enc = model.apply({"params": params}, src, None, enc_mask=src_mask)
    cross = []
    for i in range(cfg.num_decoder_layers):
        p = params[f"dec{i}"]["cross_attn"]
        cross.append({
            "k": (enc @ p["k"]["kernel"].astype(dt)).reshape(-1, T, H, hd),
            "v": (enc @ p["v"]["kernel"].astype(dt)).reshape(-1, T, H, hd)})
    return cross


def _t5_step(cfg, params, cache, cross, src_mask, dec_bias_tbl, tok, idx):
    """One decoder token against the self-attn cache + fixed cross K/V.

    ``dec_bias_tbl`` is the (T_dec, H, T_dec) causal rel-bias tensor
    precomputed outside the scan; row ``idx`` biases this query (per-row
    rows when ``idx`` is ``(B,)``)."""
    cache, raw = _as_cache(cache)
    H, hd = cfg.num_heads, cfg.head_dim
    dt = cfg.dtype
    x = params["embedding"][tok].astype(dt)               # (B, D)
    for i in range(cfg.num_decoder_layers):
        p = params[f"dec{i}"]
        h = _rmsnorm(x, p["ln1"], cfg.ln_eps)
        q = (h @ p["self_attn"]["q"]["kernel"].astype(dt)) \
            .reshape(-1, H, hd)
        k = (h @ p["self_attn"]["k"]["kernel"].astype(dt)) \
            .reshape(-1, H, hd)
        v = (h @ p["self_attn"]["v"]["kernel"].astype(dt)) \
            .reshape(-1, H, hd)
        cache, ck, cv = cache.update(i, k, v, idx)
        # T5: no 1/sqrt scaling; additive causal rel bias for this row.
        if jnp.ndim(idx) == 0:
            b = jax.lax.dynamic_index_in_dim(
                dec_bias_tbl, idx, axis=0, keepdims=False)[None]
        else:                                 # (B,) rows -> (B, H, T_tbl)
            b = dec_bias_tbl[idx]
        t = ck.shape[1]
        s = jnp.einsum("bhd,bthd->bht", q, ck).astype(jnp.float32) \
            + b[..., :t]
        s = jnp.where(_key_mask(t, idx, 1), s, -1e30)
        a = jax.nn.softmax(s, -1).astype(dt)
        o = jnp.einsum("bht,bthd->bhd", a, cv)
        x = x + o.reshape(-1, H * hd) \
            @ p["self_attn"]["o"]["kernel"].astype(dt)
        # Cross-attention over the fixed encoder K/V; no bias, masked.
        h = _rmsnorm(x, p["ln2"], cfg.ln_eps)
        q = (h @ p["cross_attn"]["q"]["kernel"].astype(dt)) \
            .reshape(-1, H, hd)
        s = jnp.einsum("bhd,bthd->bht", q, cross[i]["k"]) \
            .astype(jnp.float32)
        s = jnp.where(src_mask[:, None, :], s, -1e30)
        a = jax.nn.softmax(s, -1).astype(dt)
        # Fully-padded source rows: zero the attention instead of a
        # uniform softmax over -inf (the shared dense path's contract).
        a = jnp.where(src_mask.any(-1)[:, None, None], a,
                      jnp.zeros_like(a))
        o = jnp.einsum("bht,bthd->bhd", a, cross[i]["v"])
        x = x + o.reshape(-1, H * hd) \
            @ p["cross_attn"]["o"]["kernel"].astype(dt)
        h = _rmsnorm(x, p["ln3"], cfg.ln_eps)
        g = jax.nn.gelu(h @ p["mlp"]["wi_0"]["kernel"].astype(dt))
        u = h @ p["mlp"]["wi_1"]["kernel"].astype(dt)
        x = x + (g * u) @ p["mlp"]["wo"]["kernel"].astype(dt)
    x = _rmsnorm(x, params["dec_norm"], cfg.ln_eps)
    return (cache.layers if raw else cache), \
        x.astype(jnp.float32) @ params["lm_head"].T


def t5_encode(model: Any, cfg, params, src, src_mask):
    """Public name for the one-shot encoder + cross-attention K/V pass
    (:func:`_t5_encode`): the serving engine runs this once per admitted
    request and scatters the rows into its per-slot cross buffers."""
    return _t5_encode(model, cfg, params, src, src_mask)


def t5_decoder_bias(cfg, params, t_dec: int) -> jnp.ndarray:
    """The (T_dec, H, T_dec) causal relative-position bias tensor the
    decoder self-attention adds — precomputed once per generation (and
    once per engine at its ``max_len``: the bucketing depends only on
    relative offsets, so row ``idx`` of a larger table equals row ``idx``
    of a smaller one wherever the key mask admits)."""
    from horovod_tpu.models.t5 import relative_position_bucket
    rel = jnp.arange(t_dec)[None, :] - jnp.arange(t_dec)[:, None]
    buckets = relative_position_bucket(
        rel, bidirectional=False, num_buckets=cfg.rel_buckets,
        max_distance=cfg.rel_max_distance)
    dec_bias = params["dec_rel"]["rel_bias"][buckets]     # (T, T, H)
    return dec_bias.transpose(0, 2, 1)                    # (Tq, H, Tk)


def t5_generate(model: Any, params: Any, src: jnp.ndarray,
                max_new_tokens: int, *, temperature: float = 0.0,
                top_k: Optional[int] = None,
                rng: Optional[jax.Array] = None,
                eos_id: Optional[int] = None,
                src_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Seq2seq decode: ``(B, T_src) -> (B, max_new_tokens)`` target ids.

    The encoder (and every layer's cross-attention K/V) runs once; the
    decoder starts from T5's pad/start token and scans with a cached
    self-attention. Sampling controls as :func:`generate`.
    """
    from horovod_tpu.models.t5 import T5
    if not isinstance(model, T5):
        raise TypeError(f"t5_generate needs a T5 model, got "
                        f"{type(model).__name__}")
    cfg = model.cfg
    if max_new_tokens <= 0:
        raise ValueError(
            f"max_new_tokens must be > 0, got {max_new_tokens}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng=")
    if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
        raise ValueError(f"top_k must be in [1, vocab_size="
                         f"{cfg.vocab_size}], got {top_k}")
    params = jax.tree_util.tree_map(jnp.asarray, params)
    src = src.astype(jnp.int32)
    B = src.shape[0]
    if src_mask is None:
        src_mask = src != cfg.pad_id
    cross = _t5_encode(model, cfg, params, src, src_mask)

    T_dec = int(max_new_tokens)
    dec_bias = t5_decoder_bias(cfg, params, T_dec)

    cache = init_cache(cfg, B, T_dec)
    keys = (jax.random.split(rng, T_dec) if rng is not None
            else jnp.zeros((T_dec, 2), jnp.uint32))

    def body(carry, t):
        cache, tok, done = carry
        cache, logits = _t5_step(cfg, params, cache, cross, src_mask,
                                 dec_bias, tok, t)
        nxt = _sample(logits, temperature, top_k, keys[t])
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        return (cache, nxt, done), nxt

    start = jnp.full((B,), cfg.pad_id, jnp.int32)         # T5: pad = BOS
    (_, _, _), out = jax.lax.scan(
        body, (cache, start, jnp.zeros((B,), bool)), jnp.arange(T_dec))
    return out.T


# ---------------------------------------------------------------------------
# decode-step registry: one decode program per family, two drivers
# (``generate()`` here, the continuous-batching engine in
# ``horovod_tpu.serving``) — the factoring that keeps engine output
# token-identical to offline generation by construction.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DecodeFamily:
    """One family's decode surface: the per-token step plus the cache
    geometry (layers x kv-heads x head-dim) both drivers allocate from.

    ``step(cfg, params, cache, tok, pos, extras=None)`` advances every
    row one token: ``cache`` is the dense dict or a protocol object,
    ``pos`` a scalar or ``(B,)``, ``extras`` family side-state (T5's
    cross K/V + source mask + bias table; ``None`` for decoder-only).
    """

    name: str
    step: Callable[..., Tuple[Any, jnp.ndarray]]
    num_layers: Callable[[Any], int]
    kv_heads: Callable[[Any], int]
    head_dim: Callable[[Any], int]
    validate: Callable[[Any], None]


def _reject_moe(cfg) -> None:
    if getattr(cfg, "num_experts", 0) > 0:
        raise NotImplementedError(
            "generate() does not decode MoE configs yet")


def _gpt2_entry(cfg, params, cache, tok, pos, extras=None):
    return _gpt2_step(cfg, params, cache, tok, pos)


def _llama_entry(cfg, params, cache, tok, pos, extras=None):
    return _llama_step(cfg, params, cache, tok, pos)


def _t5_entry(cfg, params, cache, tok, pos, extras=None):
    if extras is None:
        raise ValueError("the T5 decode step needs extras= with "
                         "{'cross', 'src_mask', 'dec_bias'}")
    return _t5_step(cfg, params, cache, extras["cross"],
                    extras["src_mask"], extras["dec_bias"], tok, pos)


_FAMILIES = {
    "GPT2Config": DecodeFamily(
        name="gpt2", step=_gpt2_entry,
        num_layers=lambda c: c.num_layers,
        kv_heads=lambda c: c.num_heads,
        head_dim=lambda c: c.d_model // c.num_heads,
        validate=_reject_moe),
    "LlamaConfig": DecodeFamily(
        name="llama", step=_llama_entry,
        num_layers=lambda c: c.num_layers,
        kv_heads=lambda c: c.num_kv_heads,
        head_dim=lambda c: c.d_model // c.num_heads,
        validate=_reject_moe),
    "T5Config": DecodeFamily(
        name="t5", step=_t5_entry,
        num_layers=lambda c: c.num_decoder_layers,
        kv_heads=lambda c: c.num_heads,
        head_dim=lambda c: c.head_dim,
        validate=lambda c: None),
}


# Families that are trained here and not served, and what serving them
# lacks (ROADMAP.md, Reach).
_TRAINING_ONLY = {
    "SDARConfig": "block-diffusion sampling yields a block a step and the "
                  "expert layer has no decode path",
    "LFM2Config": "a conv layer's decode state (its last K - 1 gated "
                  "inputs) has no place in the cache beside keys and "
                  "values, and the expert layer has no decode path",
    "Glm4MoeLiteConfig": "latent attention's cache of one latent and one "
                         "rotated key a token, and the absorbed decode "
                         "path that reads it, are not built, and the "
                         "expert layer and its shared expert have no "
                         "decode path",
    "SmallThinkerConfig": "the cache has one kind of block table and the "
                          "decode kernels no window (a window layer's "
                          "blocks would have to be a ring of its last "
                          "sliding_window keys beside a global layer's "
                          "whole row), and the expert layer has no decode "
                          "path",
}


def decode_family(cfg) -> DecodeFamily:
    """The :class:`DecodeFamily` for a model config (by config type)."""
    name = type(cfg).__name__
    fam = _FAMILIES.get(name)
    if fam is None:
        if name in _TRAINING_ONLY:
            raise TypeError(f"{name} is trained here and not served: "
                            f"{_TRAINING_ONLY[name]}")
        raise TypeError(
            f"no decode family registered for {name}; "
            f"known: {sorted(_FAMILIES)}")
    return fam


def decode_step(cfg) -> Callable[..., Tuple[Any, jnp.ndarray]]:
    """``(params, cache, tok, pos, extras=None) -> (cache, logits)`` —
    the family's per-token decode step bound to ``cfg``."""
    fam = decode_family(cfg)
    fam.validate(cfg)

    def step(params, cache, tok, pos, extras=None):
        return fam.step(cfg, params, cache, tok, pos, extras)

    return step


def decode_verify_step(cfg) -> Callable[..., Tuple[Any, jnp.ndarray,
                                                   jnp.ndarray]]:
    """K-token verify variant of :func:`decode_step` for speculative
    decode: ``(params, cache, tok_seq, pos0, counts=None, extras=None,
    mask_fn=None) -> (cache, first_logits, greedy)``.

    Feeds ``tok_seq`` — ``(K, B)`` token ids, row 0 the committed token
    and rows 1.. the proposer's drafts — through K chained decode steps
    of the SAME per-family step function (``lax.scan``, one compiled
    program for any K), each lane advancing from its own ``pos0``.
    Returns the step-0 logits (``(B, V)`` fp32 — what a K=1 caller would
    have gotten, used by sampling paths) and the greedy pick after every
    step (``(K, B)`` via :func:`greedy_token` — the verify chain:
    ``greedy[j]`` is the model's token AFTER seeing ``tok_seq[:j+1]``,
    so a draft ``tok_seq[j+1]`` is accepted iff it equals ``greedy[j]``
    and everything before it was accepted).

    ``counts`` (``(B,)``) is each lane's number of live steps;
    ``mask_fn(cache, lane)`` applies the per-step lane mask (the paged
    cache's ``with_active`` — steps ``j >= counts`` write to the trash
    block, so rejected drafts never dirty real cache state). Both
    default to None for the run-all-K dense case. With ``K == 1`` this
    is exactly the classic one-token decode step, which is how the
    serving engine keeps ``decode_compiles == 1``: the verify scan IS
    its only decode program, at every ``spec_k`` including 0.
    """
    fam = decode_family(cfg)
    fam.validate(cfg)
    vocab = cfg.vocab_size

    def verify(params, cache, tok_seq, pos0, counts=None, extras=None,
               mask_fn=None):
        pos0 = jnp.asarray(pos0, jnp.int32)
        first0 = jnp.zeros((tok_seq.shape[1], vocab), jnp.float32)

        def body(carry, inp):
            cache, first = carry
            tok, j = inp
            if mask_fn is not None and counts is not None:
                cache = mask_fn(cache, j < counts)
            cache, logits = fam.step(cfg, params, cache, tok, pos0 + j,
                                     extras)
            first = jnp.where(j == 0, logits.astype(jnp.float32), first)
            return (cache, first), greedy_token(logits).astype(jnp.int32)

        K = tok_seq.shape[0]
        (cache, first), greedy = jax.lax.scan(
            body, (cache, first0),
            (tok_seq, jnp.arange(K, dtype=jnp.int32)))
        return cache, first, greedy

    return verify


def init_cache(cfg, batch: int, total_len: int):
    """The dense KV cache both drivers' shapes derive from: one K and one
    V of ``(B, T, kv_heads, head_dim)`` per layer, in the model's compute
    dtype (GQA caches stay at kv width — the memory saving grouped heads
    exist for)."""
    fam = decode_family(cfg)
    kv, hd = fam.kv_heads(cfg), fam.head_dim(cfg)
    return {i: {"k": jnp.zeros((batch, total_len, kv, hd), cfg.dtype),
                "v": jnp.zeros((batch, total_len, kv, hd), cfg.dtype)}
            for i in range(fam.num_layers(cfg))}


def _step_fn(model):
    from horovod_tpu.models.gpt2 import GPT2
    from horovod_tpu.models.llama import Llama
    if isinstance(model, Llama):
        fam = _FAMILIES["LlamaConfig"]
    elif isinstance(model, GPT2):
        fam = _FAMILIES["GPT2Config"]
    else:
        why = _TRAINING_ONLY.get(type(getattr(model, "cfg", None)).__name__)
        raise TypeError(f"generate() supports GPT2 and Llama models, got "
                        f"{type(model).__name__}"
                        + (f" (trained here and not served: {why})"
                           if why else ""))
    fam.validate(model.cfg)
    return fam, fam.kv_heads(model.cfg)


def greedy_token(logits, rel_tol: float = 1e-5):
    """Deterministic greedy pick with a tolerance tie-break.

    Plain ``argmax`` is bit-fragile: two lowerings of the same model
    (cached decode vs full forward, fused vs unfused) accumulate fp32
    sums in different orders, and a near-tie then flips the picked token.
    This selects the LOWEST token id whose logit is within
    ``rel_tol * max(1, |top|)`` of the maximum — any two lowerings whose
    logits agree to well under the tolerance pick the same token, and
    ties break identically everywhere. The parity oracles in
    ``tests/test_generate.py`` use the same rule.
    """
    m = jnp.max(logits, axis=-1, keepdims=True)
    eps = rel_tol * jnp.maximum(jnp.abs(m), 1.0)
    # argmax of bool returns the FIRST True: lowest index within band.
    return jnp.argmax(logits >= m - eps, axis=-1)


def _sample(logits, temperature, top_k, key):
    if temperature == 0.0:
        return greedy_token(logits)
    logits = logits / temperature
    if top_k is not None:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits >= kth, logits, -1e30)
    return jax.random.categorical(key, logits, axis=-1)


def generate(model: Any, params: Any, prompt: jnp.ndarray,
             max_new_tokens: int, *, temperature: float = 0.0,
             top_k: Optional[int] = None,
             rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None) -> jnp.ndarray:
    """``(B, P) prompt -> (B, P + max_new_tokens)`` token matrix.

    The prompt is teacher-forced through the same cached decode steps
    that sample the continuation (one compiled ``lax.scan``; prefill
    optimisation is a throughput concern the training framework doesn't
    chase). ``temperature=0`` is greedy; ``eos_id`` freezes a row once
    it samples EOS (further positions repeat ``eos_id``).
    """
    fam, _ = _step_fn(model)
    step = fam.step
    cfg = model.cfg
    # Converted checkpoints arrive as numpy trees; decode indexes tables
    # with traced token ids, which needs device arrays.
    params = jax.tree_util.tree_map(jnp.asarray, params)
    B, P = prompt.shape
    if max_new_tokens < 0:
        raise ValueError(
            f"max_new_tokens must be >= 0, got {max_new_tokens}")
    total = P + int(max_new_tokens)
    if total > cfg.max_seq_len:
        raise ValueError(f"prompt {P} + {max_new_tokens} new tokens "
                         f"exceeds max_seq_len={cfg.max_seq_len}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng=")
    if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
        raise ValueError(f"top_k must be in [1, vocab_size="
                         f"{cfg.vocab_size}], got {top_k}")
    cache = init_cache(cfg, B, total)
    prompt = prompt.astype(jnp.int32)
    keys = (jax.random.split(rng, total) if rng is not None
            else jnp.zeros((total, 2), jnp.uint32))

    def body(carry, t):
        cache, tok, done = carry
        cache, logits = step(cfg, params, cache, tok, t)
        nxt = _sample(logits, temperature, top_k, keys[t])
        # teacher-force inside the prompt; then sample
        in_prompt = t + 1 < P
        forced = prompt[:, jnp.minimum(t + 1, P - 1)]
        nxt = jnp.where(in_prompt, forced, nxt)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | ((~in_prompt) & (nxt == eos_id))
        return (cache, nxt, done), nxt

    done0 = jnp.zeros((B,), bool)
    (_, _, _), out = jax.lax.scan(
        body, (cache, prompt[:, 0], done0), jnp.arange(total - 1))
    return jnp.concatenate([prompt[:, :1], out.T], axis=1)
