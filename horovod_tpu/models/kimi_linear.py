"""Kimi-Linear-style decoder: chunked gated delta-rule layers (KDA) beside
position-free latent attention, a dense SwiGLU first and routed experts
with a shared one after.

Every block is ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``,
RMSNorm eps 1e-5, no bias anywhere (the ``kimi_linear`` family; the Kimi
Linear technical report, arXiv 2510.26692):

* ``Mixer`` is KDA in the layers that ``mla_layers`` does not name. For each
  of ``kda_heads`` heads of ``kda_head_dim``, from the normed input ``x``:
  ``q, k, v = SiLU(conv4(W_q x)), SiLU(conv4(W_k x)), SiLU(conv4(W_v x))``
  (a causal depthwise convolution of ``conv_size`` taps a channel, no bias,
  ``ops/short_conv.causal_conv_silu``); ``q`` and ``k`` through an L2 norm
  a head; a log-decay a key channel ``g = -exp(A_log[h]) softplus(W_f_up
  W_f_down x + dt_bias)`` (rank ``kda_head_dim``) and a write strength
  ``beta = sigmoid(W_beta x)[h]``; then the gated delta rule
  (``ops/kda.chunk_kda``: ``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} +
  beta k v^T``, ``o_t = S_t^T q_t / sqrt(d_k)``); out through a per-head
  RMSNorm of ``kda_head_dim`` (one weight for all heads) times ``sigmoid(W_g_up
  W_g_down x)``, and ``o_proj``.
* ``Mixer`` is latent attention (``models/layers.LatentAttention``) in the
  layers ``mla_layers`` names, with no query bottleneck and nothing rotated
  (``mla_use_nope``: the convolutions and decays of KDA carry position),
  its values ``v_head_dim`` wide, narrower than its keys.
* ``FFN`` is a dense SwiGLU of ``d_ff`` in the first ``num_dense_layers``
  blocks; in the rest the dropless routed layer (``ops/moe.RoutedExperts``,
  told which experts it holds) under the sigmoid rule (the top ``top_k`` of
  score plus a per-expert selection bias, gates the unbiased scores over
  their sum ``+ 1e-20``, times ``routed_scale``) **plus a shared expert**.

After the last block one more RMSNorm and the untied head. The selection
bias is a buffer and an input (``expert_bias``, a row a layer), as in the
GLM-4 family. Training only: the serving cache has no place for a recurrent
state.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu import tracing as _tracing
from horovod_tpu.models.layers import (LatentAttention, RMSNorm,
                                       next_token_cross_entropy)
from horovod_tpu.models.remat import remat_block

__all__ = ["KimiLinear", "KimiLinearConfig", "latent_attention", "loss_fn",
           "layer_kinds"]

_NORM_EPS = 1e-20       # what the family adds to the sum of the chosen gates
_L2_EPS = 1e-6          # inside the square root of q's and k's L2 norms
# the decay's initialisation (the family's convention): A = U(1, 16) a
# head, dt log-uniform in [1e-3, 1e-1] a channel, dt_bias its inverse
# softplus
_A_RANGE = (1.0, 16.0)
_DT_RANGE = (1e-3, 1e-1)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """Shapes of the decoder and what of it is held here; the defaults are
    Kimi-Linear-48B-A3B's published ones with every expert and the whole
    vocabulary held."""
    TRAINING_ONLY = ("a KDA layer's recurrent state (d_k x d_v a head) and "
                     "its three convolutions' last K - 1 inputs have no "
                     "place in the cache beside a latent cache, no decode "
                     "step advances them a token at a time, and the expert "
                     "layer has no decode path")
    vocab_size: int = 163840
    num_layers: int = 27
    num_dense_layers: int = 1                 # first_k_dense_replace
    mla_layers: Tuple[int, ...] = (3, 7, 11, 15, 19, 23, 26)   # from 0
    d_model: int = 2304
    # KDA
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4
    # latent attention
    num_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True                 # no RoPE: no rope_theta
    # feed-forward
    d_ff: int = 9216
    d_expert: int = 1024
    experts_total: int = 256
    experts_held: Tuple[int, int] = (0, 256)  # (first, count) held here
    top_k: int = 8
    shared_experts: int = 1
    norm_topk: bool = True
    routed_scale: float = 2.446
    rms_eps: float = 1e-5
    embed_std: float = 0.02
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "dense"                  # "dense" | "flash"
    flash_blocks: Optional[tuple] = None
    remat: bool = False
    remat_policy: str = "full"
    ep_axis: Optional[str] = None

    @staticmethod
    def tiny(**kw) -> "KimiLinearConfig":
        """A size for CPU tests that keeps the kinds of layer and their
        order (a dense KDA block, then KDA, KDA, latent attention, KDA),
        values narrower than keys, several experts a position."""
        base = dict(vocab_size=256, num_layers=5, mla_layers=(3,),
                    d_model=32, kda_heads=2, kda_head_dim=16,
                    num_heads=2, kv_lora_rank=16,
                    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=8,
                    d_ff=48, d_expert=16, experts_total=8,
                    experts_held=(0, 8), top_k=2)
        base.update(kw)
        return KimiLinearConfig(**base)


def layer_kinds(cfg: KimiLinearConfig):
    """``(mixer, feed-forward)`` of every block: ``"kda"`` or ``"mla"``,
    ``"dense"`` or ``"routed"``."""
    return [("mla" if i in cfg.mla_layers else "kda",
             "dense" if i < cfg.num_dense_layers else "routed")
            for i in range(cfg.num_layers)]


def latent_attention(cfg: KimiLinearConfig, **kw) -> LatentAttention:
    """The family's latent attention from ``cfg``; ``kw`` names it."""
    return LatentAttention(
        cfg.num_heads, cfg.d_model, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.rms_eps, cfg.dtype,
        q_lora_rank=cfg.q_lora_rank,
        rope_theta=None if cfg.mla_use_nope else cfg.rope_theta,
        impl=cfg.attention, flash_blocks=cfg.flash_blocks, **kw)


def _dense(cfg, width, name):
    return nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)


def _taps(key, shape, dtype=jnp.float32):
    # PyTorch's Conv1d default for a depthwise filter: U(-1/sqrt(K), ..)
    bound = 1.0 / math.sqrt(shape[-1])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, *_A_RANGE))


def _dt_bias(key, shape, dtype=jnp.float32):
    lo, hi = (math.log(x) for x in _DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)


def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + _L2_EPS)


class KDA(nn.Module):
    """A KDA layer's mixer (the module's docstring) on the normed input
    (B, T, d)."""
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.ops.kda import chunk_kda
        from horovod_tpu.ops.short_conv import causal_conv_silu
        cfg = self.cfg
        B, T, _ = x.shape
        H, D, K = cfg.kda_heads, cfg.kda_head_dim, cfg.conv_size
        with _tracing.scope("kimi/kda_in"):
            def conv(name):
                taps = self.param(name + "_conv", _taps, (H * D, K))
                return causal_conv_silu(_dense(cfg, H * D, name + "_proj")(x),
                                        taps).reshape(B, T, H, D)
            q, k, v = conv("q"), conv("k"), conv("v")
            q = _l2norm(q).astype(cfg.dtype)
            k = _l2norm(k).astype(cfg.dtype)
            a_log = self.param("A_log", _a_log, (H,))
            dt_bias = self.param("dt_bias", _dt_bias, (H * D,))
            f = _dense(cfg, H * D, "f_b")(_dense(cfg, D, "f_a")(x))
            g = (-jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (f.astype(jnp.float32) + dt_bias).reshape(B, T, H, D)))
            beta = jax.nn.sigmoid(
                _dense(cfg, H, "b_proj")(x).astype(jnp.float32))
        # what the core is given, for a look at it alone (the benchmark's
        # check); nothing is kept where "intermediates" is not mutable
        self.sow("intermediates", "core_in", (q, k, v, g, beta))
        with _tracing.scope("kimi/kda"):
            o = chunk_kda(q, k, v, g, beta)
        with _tracing.scope("kimi/kda_out"):
            o = RMSNorm(cfg.rms_eps, name="o_norm")(o)
            gate = jax.nn.sigmoid(
                _dense(cfg, H * D, "g_b")(_dense(cfg, D, "g_a")(x)))
            return _dense(cfg, cfg.d_model, "o_proj")(
                (o.reshape(B, T, H * D) * gate).astype(cfg.dtype))


class Block(nn.Module):
    """Block ``layer``: KDA or latent attention, then a dense SwiGLU or the
    routed and shared experts (:func:`layer_kinds`). ``select_bias``
    (experts_total,) is the routed layer's; None routes by the scores
    alone."""
    cfg: KimiLinearConfig
    layer: int

    @nn.compact
    def __call__(self, x, select_bias=None):
        from horovod_tpu.ops.moe import RoutedExperts, SharedExpert
        cfg = self.cfg
        mixer, ffn = layer_kinds(cfg)[self.layer]
        with _tracing.scope("kimi/block"):
            u = RMSNorm(cfg.rms_eps, name="norm_in")(x)
            if mixer == "mla":
                with _tracing.scope("kimi/mla"):
                    x = x + latent_attention(cfg, name="attn")(u)
            else:
                x = x + KDA(cfg, name="kda")(u)
            u = RMSNorm(cfg.rms_eps, name="norm_post")(x)
            if ffn == "dense":
                with _tracing.scope("kimi/dense_mlp"):
                    return x + SharedExpert(cfg.d_ff, cfg.dtype,
                                            name="mlp")(u)
            y = RoutedExperts(
                cfg.experts_total, cfg.experts_held, cfg.top_k, cfg.d_expert,
                cfg.norm_topk, cfg.dtype, cfg.ep_axis, score="sigmoid",
                norm_eps=_NORM_EPS, scale=cfg.routed_scale, name="moe")(
                    u, select_bias)
            if cfg.shared_experts:
                with _tracing.scope("kimi/shared_expert"):
                    y = y + SharedExpert(cfg.d_expert * cfg.shared_experts,
                                         cfg.dtype, name="shared")(u)
            return x + y


class KimiLinear(nn.Module):
    """The decoder; see the module's docstring. Parameters: ``wte``,
    ``lm_head`` (used by :func:`loss_fn`), ``h<i>`` and ``norm_f``."""
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, tokens, expert_bias=None):
        """The trunk's output after the final norm (B, T, d_model).
        ``expert_bias`` (num_layers, experts_total) fp32 is the routers'
        selection bias, a row a layer (the dense layers' rows are not
        read); None routes by the scores alone."""
        from horovod_tpu.ops.kda import chunks_run, saved_state_bytes
        cfg = self.cfg
        B, T = tokens.shape
        wte = self.param("wte", nn.initializers.normal(cfg.embed_std),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        self.param("lm_head", nn.initializers.normal(0.02),
                   (cfg.vocab_size, cfg.d_model), jnp.float32)
        kda = sum(1 for m, _ in layer_kinds(cfg) if m == "kda")
        H, D = cfg.kda_heads, cfg.kda_head_dim
        _tracing.note_routing(
            kda_chunks=chunks_run(T) * B * H * kda,
            kda_state_saved_bytes=kda * saved_state_bytes(B, T, H, D, D))
        bias = lambda i: None if expert_bias is None else expert_bias[i]
        x = wte[tokens].astype(cfg.dtype)
        block = remat_block(Block, cfg)
        for i in range(cfg.num_layers):
            x = block(cfg, i, name=f"h{i}")(x, bias(i))
        return RMSNorm(cfg.rms_eps, name="norm_f")(x)


def loss_fn(model: KimiLinear, params, tokens, expert_bias=None):
    """Mean cross entropy of the next token over the ``T - 1`` positions of
    each row that have one, through the untied head over the rows of the
    vocabulary held here (log-sum-exp minus the target's logit)."""
    hidden = model.apply({"params": params}, tokens, expert_bias)
    with _tracing.scope("kimi/loss_head"):
        return next_token_cross_entropy(hidden, params["lm_head"], tokens)
