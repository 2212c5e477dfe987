"""Decoder parts that more than one family of the zoo uses.

RMSNorm, RoPE, the grouped-query expansion, :class:`GroupedQueryAttention`
(QK-norm or not, RoPE or not, a causal, window or block-diffusion mask),
:class:`LatentAttention` (a query bottleneck or not, positions or not) and
the next-token head. A family keeps its config, block, model and loss, and
fills these parts' fields from its config.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu import tracing as _tracing

__all__ = ["RMSNorm", "apply_rope", "repeat_kv", "GroupedQueryAttention",
           "LatentAttention", "next_token_cross_entropy"]


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """Rotary position embedding over (B, T, H, D) with (T,) or (B, T)
    positions.

    Pair-rotation ("rotate half") form in fp32, cast back to x.dtype.
    Positions are explicit so sequence-parallel shards pass their GLOBAL
    token positions (contiguous offset or striped interleave) and rotation
    commutes with the ring: every shard rotates its own K before any hop.
    (B, T) positions carry per-row packing offsets (pos-in-document).
    """
    d2 = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = positions.astype(jnp.float32)[..., None] * freq  # (..., T, d2)
    if ang.ndim == 2:                                      # (T, d2)
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    else:                                                  # (B, T, d2)
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d2], xf[..., d2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def repeat_kv(k: jnp.ndarray, v: jnp.ndarray, num_heads: int):
    """Grouped-query attention: expand the key/value heads of (B, T, Hkv, D)
    to ``num_heads``, each serving ``num_heads // Hkv`` query heads in
    order, so that the attention ops see plain multi-head shapes."""
    q_per_kv = num_heads // k.shape[2]
    if q_per_kv == 1:
        return k, v
    return jnp.repeat(k, q_per_kv, axis=2), jnp.repeat(v, q_per_kv, axis=2)


class RMSNorm(nn.Module):
    """fp32 root-mean-square norm with a learned scale (no mean removal)."""
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        return (y * scale).astype(x.dtype)


def _dense(width, dtype, name):
    return nn.Dense(width, use_bias=False, dtype=dtype, name=name)


class GroupedQueryAttention(nn.Module):
    """Grouped-query attention over (B, T, d_model), out to ``d_model``.

    ``qk_norm_eps`` None: no QK-norm; else an RMSNorm of that eps over every
    query and key head (``q_norm``, ``k_norm``), before RoPE.
    ``rope_theta`` None: no position enters the scores. The mask is causal,
    ``window`` keys wide where set; with ``diffusion_block`` set the row is
    ``[noisy ; clean]`` under the block-diffusion mask of that block length
    (``ops/attention.block_diffusion_mask``) instead."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_model: int
    dtype: Any
    impl: str = "dense"              # "dense" | "flash"
    flash_blocks: Optional[tuple] = None
    qk_norm_eps: Optional[float] = None
    rope_theta: Optional[float] = None
    window: Optional[int] = None
    diffusion_block: Optional[int] = None

    @nn.compact
    def __call__(self, x, positions=None):
        """``positions`` (T,) are RoPE's; None: ``0 .. T - 1``."""
        B, T, _ = x.shape
        H, Hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        if H % Hkv:
            raise ValueError(f"num_kv_heads={Hkv} must divide num_heads={H}")
        proj = lambda heads, name: _dense(heads * hd, self.dtype, name)(
            x).reshape(B, T, heads, hd)
        q, k, v = proj(H, "wq"), proj(Hkv, "wk"), proj(Hkv, "wv")
        if self.qk_norm_eps is not None:
            q = RMSNorm(self.qk_norm_eps, name="q_norm")(q)
            k = RMSNorm(self.qk_norm_eps, name="k_norm")(k)
        if self.rope_theta is not None:
            if positions is None:
                positions = jnp.arange(T)
            q = apply_rope(q, positions, self.rope_theta)
            k = apply_rope(k, positions, self.rope_theta)
        k, v = repeat_kv(k, v, H)
        from horovod_tpu.ops.attention import multihead_attention
        diffusion = (None if self.diffusion_block is None
                     else (T // 2, self.diffusion_block))
        o = multihead_attention(
            q, k, v, impl=self.impl, causal=diffusion is None,
            out_dtype=self.dtype, flash_blocks=self.flash_blocks,
            block_diffusion=diffusion, window=self.window)
        return _dense(self.d_model, self.dtype, "wo")(
            o.reshape(B, T, H * hd))


class LatentAttention(nn.Module):
    """Causal latent attention in its expanded form (DeepSeek-V3's).

    Queries through a normed bottleneck of ``q_lora_rank`` (``q_a``,
    ``q_norm``, ``q_b``), or with it None straight from ``x`` (``q``). Keys
    and values from one normed latent of ``kv_lora_rank`` a token and one
    key part of ``qk_rope_head_dim`` that **all heads share** (``kv_a``,
    ``kv_norm``, ``kv_b``), rotated with the queries' by RoPE of
    ``rope_theta`` (None: no position enters the scores). Scale ``1 /
    sqrt(qk_nope_head_dim + qk_rope_head_dim)``; values ``v_head_dim``."""
    num_heads: int
    d_model: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_eps: float
    dtype: Any
    q_lora_rank: Optional[int] = None
    rope_theta: Optional[float] = None
    impl: str = "dense"              # "dense" | "flash"
    flash_blocks: Optional[tuple] = None

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, rank = self.num_heads, self.kv_lora_rank
        nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        dense = lambda width, name: _dense(width, self.dtype, name)
        with _tracing.scope("glm4/mla_down"):
            if self.q_lora_rank is not None:
                c_q = RMSNorm(self.rms_eps, name="q_norm")(
                    dense(self.q_lora_rank, "q_a")(x))
            kv = dense(rank + rope, "kv_a")(x)
            c_kv = RMSNorm(self.rms_eps, name="kv_norm")(kv[..., :rank])
        with _tracing.scope("glm4/mla_up"):
            if self.q_lora_rank is None:
                q = dense(H * (nope + rope), "q")(x)
            else:
                q = dense(H * (nope + rope), "q_b")(c_q)
            q = q.reshape(B, T, H, nope + rope)
            kv_up = dense(H * (nope + dv), "kv_b")(c_kv).reshape(
                B, T, H, nope + dv)
            if self.rope_theta is None:
                k_rope = kv[:, :, None, rank:]
            else:
                positions = jnp.arange(T)
                q = jnp.concatenate(
                    [q[..., :nope],
                     apply_rope(q[..., nope:], positions, self.rope_theta)],
                    -1)
                # one rotated key a token, the same for every head
                k_rope = apply_rope(kv[:, :, None, rank:], positions,
                                    self.rope_theta)
            k = jnp.concatenate(
                [kv_up[..., :nope],
                 jnp.broadcast_to(k_rope, (B, T, H, rope))], -1)
            v = kv_up[..., nope:]
        with _tracing.scope("glm4/attn"):
            from horovod_tpu.ops.attention import multihead_attention
            o = multihead_attention(q, k, v, impl=self.impl, causal=True,
                                    out_dtype=self.dtype,
                                    flash_blocks=self.flash_blocks,
                                    scale=(nope + rope) ** -0.5)
            return dense(self.d_model, "o")(o.reshape(B, T, H * dv))


def next_token_cross_entropy(hidden, head, tokens, ahead: int = 1):
    """Mean cross entropy of the token ``ahead`` positions on, by ``head``.

    The logits are ``hidden`` (B, T, d) times the rows of ``head`` (V, d) in
    fp32; the mean is over the ``T - ahead`` positions of each row of
    ``tokens`` (B, T) that have a target. Log-sum-exp minus the target's
    logit, not a gather from ``log_softmax``: the (B, T, V) array of
    log-probabilities is never made (on the v5e at 4 x 8,192 x 8,192 the
    head's forward and backward take 20 ms this way and 131 ms the other,
    whose soft-max reductions XLA lays out across the position axis). The
    positions without a target are left out at the end, on (B, T) values."""
    logits = jnp.einsum("btd,vd->btv", hidden.astype(jnp.float32), head)
    target = jnp.take_along_axis(
        logits, jnp.roll(tokens, -ahead, axis=1)[..., None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - target
    return jnp.mean(nll[:, :-ahead])
