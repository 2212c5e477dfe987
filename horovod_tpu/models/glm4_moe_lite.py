"""GLM-4-MoE-Lite-style decoder: latent attention, a shared expert beside
the routed ones, and a multi-token-prediction module in the loss.

Every block is ``h = x + Attn(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``, no
bias anywhere (the ``glm4_moe_lite`` family, whose layer equations are
DeepSeek-V3's):

* ``Attn`` is latent attention in its expanded form. Queries go through a
  bottleneck of ``q_lora_rank`` with an RMSNorm inside it; keys and values
  come from one latent of ``kv_lora_rank`` a token, normed, and one RoPE key
  of ``qk_rope_head_dim`` a token that **all heads share**. A head's query
  and key are a per-head part without position (``qk_nope_head_dim``) and a
  rotated part; its key's rotated part is the shared one, broadcast. The
  scale is ``1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)``. Keys and
  values are expanded a head before the kernel; the absorbed form (the
  latent itself as the key) is a decode-time rewrite and is not here.
* ``FF`` is a dense SwiGLU in the first ``num_dense_layers`` blocks. In the
  rest it is the dropless routed layer (``ops/moe.RoutedExperts``, told
  which experts it holds) under the family's rule: a sigmoid score an
  expert, the top ``top_k`` of score plus a per-expert bias, gates the
  unbiased scores of the chosen over their sum ``+ 1e-20``, times
  ``routed_scale``; **plus a shared expert** (``ops/moe.SharedExpert``) that
  every position goes through, unweighted. The group-limited choice of the
  family is the identity at ``n_group = topk_group = 1``; other values raise.

After the last block one more RMSNorm and the untied head. The
multi-token-prediction module (``mtp``; DeepSeek-V3 section 2.2 at depth 1)
is one more block of the routed kind: it reads the trunk's normed output at
``t`` and the trunk's own embedding of token ``t + 1``, each through an
RMSNorm, joined by ``eh_proj`` (the embedding's half first), and after its
own RMSNorm the trunk's own head predicts token ``t + 2``. It runs over all
``T`` positions with the row's first token standing in for the one after its
last; the block is causal, so no position that is scored reads it.
:func:`loss_fn` is ``CE_main + mtp_weight * CE_mtp``.

The selection bias is a buffer and not a parameter: the model takes it as an
input (``expert_bias``, a row a layer and one for the module), so that it
has no gradient and no optimizer state; nothing here moves it. Training
only: the serving cache has no place for one latent a token.

RoPE and RMSNorm are ``models/llama.py``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu import tracing as _tracing
from horovod_tpu.models.llama import RMSNorm, apply_rope
from horovod_tpu.models.remat import remat_block

__all__ = ["Glm4MoeLite", "Glm4MoeLiteConfig", "loss_fn", "loss_terms"]

_NORM_EPS = 1e-20       # what the family adds to the sum of the chosen gates


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """Shapes of the decoder and what of it is held here; the defaults are
    GLM-4.7-Flash's published ones with every expert and the whole
    vocabulary held."""
    vocab_size: int = 154880         # rows of the embedding and of the head
    num_layers: int = 47             # the trunk's blocks
    num_dense_layers: int = 1        # first_k_dense_replace
    num_heads: int = 20
    d_model: int = 2048
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    d_ff: int = 10240                # the dense SwiGLU's width
    d_expert: int = 1536             # width of one expert's SwiGLU
    experts_total: int = 64          # the router's width
    experts_held: Tuple[int, int] = (0, 64)     # (first, count) held here
    top_k: int = 4
    shared_experts: int = 1          # the shared SwiGLU is this many wide
    norm_topk: bool = True
    routed_scale: float = 1.8        # routed_scaling_factor
    n_group: int = 1
    topk_group: int = 1
    mtp: int = 1                     # num_nextn_predict_layers: 0 or 1
    mtp_weight: float = 0.1          # of CE_mtp in the loss
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    embed_std: float = 0.02          # the embedding rows are N(0, embed_std)
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "dense"         # "dense" | "flash"
    flash_blocks: Optional[tuple] = None
    remat: bool = False
    remat_policy: str = "full"       # "full" | "dots" (GPT2Config docs)
    ep_axis: Optional[str] = None    # mesh axis the experts are sharded on

    @staticmethod
    def tiny(**kw) -> "Glm4MoeLiteConfig":
        """A size for CPU tests that keeps the kinds of layer and of ratio:
        a dense block and routed ones, both bottlenecks narrower than the
        heads they feed, a rotated part a third of the plain one, several
        experts a position, the module."""
        base = dict(vocab_size=256, num_layers=3, num_heads=4, d_model=32,
                    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
                    qk_rope_head_dim=4, v_head_dim=16, d_ff=48, d_expert=16,
                    experts_total=8, experts_held=(0, 8), top_k=2)
        base.update(kw)
        return Glm4MoeLiteConfig(**base)


def _dense(cfg, width, name):
    return nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)


class LatentAttention(nn.Module):
    cfg: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, _ = x.shape
        H, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        with _tracing.scope("glm4/mla_down"):
            c_q = RMSNorm(cfg.rms_eps, name="q_norm")(
                _dense(cfg, cfg.q_lora_rank, "q_a")(x))
            kv = _dense(cfg, rank + rope, "kv_a")(x)
            c_kv = RMSNorm(cfg.rms_eps, name="kv_norm")(kv[..., :rank])
        with _tracing.scope("glm4/mla_up"):
            q = _dense(cfg, H * (nope + rope), "q_b")(c_q).reshape(
                B, T, H, nope + rope)
            kv_up = _dense(cfg, H * (nope + dv), "kv_b")(c_kv).reshape(
                B, T, H, nope + dv)
            positions = jnp.arange(T)
            q = jnp.concatenate(
                [q[..., :nope],
                 apply_rope(q[..., nope:], positions, cfg.rope_theta)], -1)
            # one rotated key a token, the same for every head
            k_rope = apply_rope(kv[:, :, None, rank:], positions,
                                cfg.rope_theta)
            k = jnp.concatenate(
                [kv_up[..., :nope],
                 jnp.broadcast_to(k_rope, (B, T, H, rope))], -1)
            v = kv_up[..., nope:]
        with _tracing.scope("glm4/attn"):
            from horovod_tpu.ops.attention import multihead_attention
            o = multihead_attention(q, k, v, impl=cfg.attention, causal=True,
                                    out_dtype=cfg.dtype,
                                    flash_blocks=cfg.flash_blocks,
                                    scale=(nope + rope) ** -0.5)
            return _dense(cfg, cfg.d_model, "o")(o.reshape(B, T, H * dv))


class Block(nn.Module):
    """Block ``layer``: latent attention, then a dense SwiGLU where ``layer
    < cfg.num_dense_layers`` and routed plus shared experts elsewhere (the
    module's block is ``layer = cfg.num_layers``). ``select_bias``
    (experts_total,) is the routed layer's; None routes by the scores
    alone."""
    cfg: Glm4MoeLiteConfig
    layer: int

    @nn.compact
    def __call__(self, x, select_bias=None):
        from horovod_tpu.ops.moe import RoutedExperts, SharedExpert
        cfg = self.cfg
        with _tracing.scope("glm4/block"):
            x = x + LatentAttention(cfg, name="attn")(
                RMSNorm(cfg.rms_eps, name="norm_in")(x))
            u = RMSNorm(cfg.rms_eps, name="norm_post")(x)
            if self.layer < cfg.num_dense_layers:
                with _tracing.scope("glm4/dense_mlp"):
                    return x + SharedExpert(cfg.d_ff, cfg.dtype,
                                            name="mlp")(u)
            y = RoutedExperts(
                cfg.experts_total, cfg.experts_held, cfg.top_k, cfg.d_expert,
                cfg.norm_topk, cfg.dtype, cfg.ep_axis, score="sigmoid",
                norm_eps=_NORM_EPS, scale=cfg.routed_scale, name="moe")(
                    u, select_bias)
            if cfg.shared_experts:
                with _tracing.scope("glm4/shared_expert"):
                    y = y + SharedExpert(cfg.d_expert * cfg.shared_experts,
                                         cfg.dtype, name="shared")(u)
            return x + y


class MTP(nn.Module):
    """The multi-token-prediction module at depth 1: ``hidden`` (B, T, d) is
    the trunk's normed output, ``ahead`` (B, T, d) the trunk's embedding of
    each position's next token. Returns what the head is applied to."""
    cfg: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, hidden, ahead, select_bias=None):
        cfg = self.cfg
        with _tracing.scope("glm4/mtp"):
            m = jnp.concatenate(
                [RMSNorm(cfg.rms_eps, name="norm_e")(ahead),
                 RMSNorm(cfg.rms_eps, name="norm_h")(hidden)], axis=-1)
            m = _dense(cfg, cfg.d_model, "eh_proj")(m)
            r = remat_block(Block, cfg)(cfg, cfg.num_layers, name="block")(
                m, select_bias)
            return RMSNorm(cfg.rms_eps, name="norm_s")(r)


class Glm4MoeLite(nn.Module):
    """The decoder; see the module's docstring. Parameters: ``wte``,
    ``lm_head`` (used by :func:`loss_fn`), ``h<i>``, ``norm_f`` and
    ``mtp``."""
    cfg: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, tokens, expert_bias=None):
        """``(hidden, ahead)``: the trunk's output after the final norm
        (B, T, d_model), and the module's, which predicts two tokens ahead
        (None without the module). ``expert_bias`` (num_layers + mtp,
        experts_total) fp32 is the routers' selection bias, a row a layer
        (the dense layers' rows are not read) and the last the module's;
        None routes by the scores alone."""
        cfg = self.cfg
        if (cfg.n_group, cfg.topk_group) != (1, 1):
            raise ValueError(
                f"n_group={cfg.n_group}, topk_group={cfg.topk_group}: the "
                "group-limited choice is built for one group alone, where "
                "it is the identity")
        if cfg.mtp not in (0, 1):
            raise ValueError(f"mtp={cfg.mtp}: one module or none")
        if (cfg.attention == "flash" and
                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                != cfg.v_head_dim):
            raise ValueError(
                "the flash kernels take keys and values of one head size: "
                f"qk {cfg.qk_nope_head_dim} + {cfg.qk_rope_head_dim}, v "
                f"{cfg.v_head_dim}")
        B, T = tokens.shape
        wte = self.param("wte", nn.initializers.normal(cfg.embed_std),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        self.param("lm_head", nn.initializers.normal(0.02),
                   (cfg.vocab_size, cfg.d_model), jnp.float32)
        bias = lambda i: None if expert_bias is None else expert_bias[i]
        # what the expanded form writes before the kernels, and the latent
        # it is expanded from: both from shapes, a layer of attention each
        per_token = B * T * (cfg.num_layers + cfg.mtp) * jnp.dtype(
            cfg.dtype).itemsize
        _tracing.note_routing(
            mla_kv_expanded_bytes=per_token * cfg.num_heads * (
                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                + cfg.v_head_dim),
            mla_latent_bytes=per_token * (cfg.kv_lora_rank
                                          + cfg.qk_rope_head_dim),
            mtp_modules=cfg.mtp)
        x = wte[tokens].astype(cfg.dtype)
        block = remat_block(Block, cfg)
        for i in range(cfg.num_layers):
            x = block(cfg, i, name=f"h{i}")(x, bias(i))
        hidden = RMSNorm(cfg.rms_eps, name="norm_f")(x)
        if not cfg.mtp:
            return hidden, None
        ahead = wte[jnp.roll(tokens, -1, axis=1)].astype(cfg.dtype)
        return hidden, MTP(cfg, name="mtp")(hidden, ahead,
                                            bias(cfg.num_layers))


def _cross_entropy(hidden, head, tokens, ahead: int):
    """Mean cross entropy of the token ``ahead`` positions on, over the
    ``T - ahead`` positions of each row that have one. Log-sum-exp minus
    the target's logit (``models/lfm2.loss_fn`` says why); the positions
    without a target are left out at the end, on (B, T) values."""
    logits = jnp.einsum("btd,vd->btv", hidden.astype(jnp.float32), head)
    target = jnp.take_along_axis(
        logits, jnp.roll(tokens, -ahead, axis=1)[..., None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - target
    return jnp.mean(nll[:, :-ahead])


def loss_terms(model: Glm4MoeLite, params, tokens, expert_bias=None):
    """``(CE_main, CE_mtp)`` of ``tokens`` (B, T) through the one untied
    head over the rows of the vocabulary held here: the next token's mean
    cross entropy over ``T - 1`` positions a row, and the module's, of the
    token two ahead over ``T - 2`` (None without the module)."""
    hidden, ahead = model.apply({"params": params}, tokens, expert_bias)
    with _tracing.scope("glm4/loss_head"):
        head = params["lm_head"]
        return (_cross_entropy(hidden, head, tokens, 1),
                None if ahead is None
                else _cross_entropy(ahead, head, tokens, 2))


def loss_fn(model: Glm4MoeLite, params, tokens, expert_bias=None):
    """``CE_main + mtp_weight * CE_mtp`` (:func:`loss_terms`)."""
    main, mtp = loss_terms(model, params, tokens, expert_bias)
    return main if mtp is None else main + model.cfg.mtp_weight * mtp
