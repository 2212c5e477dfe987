"""LFM2-style hybrid decoder: gated short convolutions beside grouped-query
attention, a dense SwiGLU in the leading layers and routed experts after.

Every block is ``h = x + Op(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``, with
no bias anywhere, and what ``Op`` and ``FF`` are differs by layer (the LFM2
family's ``lfm2_moe``):

* ``Op`` is what ``layer_types[i]`` names. ``"conv"``: a projection to three
  groups of ``d`` channels, the gated short convolution of
  ``ops/short_conv.gated_short_conv`` (``K`` causal depthwise taps between an
  input and an output gate) and a projection back. ``"full_attention"``:
  grouped-query causal attention with an RMSNorm over every query and key
  head before RoPE.
* ``FF`` is a dense SwiGLU in the first ``num_dense_layers`` blocks and the
  dropless routed expert layer (``ops/moe.RoutedExperts``, told which
  experts it holds) in the rest, under the family's routing rule: a sigmoid
  score an expert, the top ``top_k`` of score **plus a per-expert bias**,
  gates that are the unbiased scores of the chosen, normalised over them
  with ``+ 1e-6`` and scaled by ``routed_scale``.

The bias is a buffer and not a parameter: the model takes it as an input
(``expert_bias``, a row a layer) beside the tokens, so that it has no
gradient and no optimizer state, and whoever trains the model holds it. The
rule by which the family moves it between steps is not published; nothing
here moves it. The head is the embedding's own rows (tied), after one more
RMSNorm. Training only: a conv layer's state while decoding (its last ``K -
1`` gated inputs) has no place in the serving cache.

RMSNorm, the grouped-query attention and the head are ``models/layers.py``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu import tracing as _tracing
from horovod_tpu.models.layers import (GroupedQueryAttention, RMSNorm,
                                       next_token_cross_entropy)
from horovod_tpu.models.remat import remat_block
from horovod_tpu.ops.short_conv import gated_short_conv

__all__ = ["LFM2", "LFM2Config", "loss_fn"]

_PERIOD = ("conv", "conv", "full_attention", "conv")


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    """Shapes of the decoder and what of it is held here; the defaults are
    LFM2-24B-A2B's published ones with every expert and the whole vocabulary
    held."""
    TRAINING_ONLY = ("a conv layer's decode state (its last K - 1 gated "
                     "inputs) has no place in the cache beside keys and "
                     "values, and the expert layer has no decode path")
    vocab_size: int = 65536          # rows of the tied embedding held
    num_layers: int = 40
    layer_types: Tuple[str, ...] = _PERIOD * 10    # one a layer
    num_dense_layers: int = 2        # leading blocks with a dense SwiGLU
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    d_model: int = 2048
    d_ff: int = 11776                # the dense SwiGLU's width
    d_expert: int = 1536             # width of one expert's SwiGLU
    conv_taps: int = 3               # conv_L_cache
    experts_total: int = 64          # the router's width
    experts_held: Tuple[int, int] = (0, 64)     # (first, count) held here
    top_k: int = 4
    norm_topk: bool = True
    routed_scale: float = 1.0        # routed_scaling_factor
    use_expert_bias: bool = True
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
    def tiny(**kw) -> "LFM2Config":
        """A size for CPU tests that keeps the kinds of layer and of ratio:
        one dense block, both operators, more query heads than key/value
        heads, several experts a position."""
        base = dict(vocab_size=256, num_layers=3,
                    layer_types=("conv", "full_attention", "conv"),
                    num_dense_layers=1, num_heads=4, num_kv_heads=2,
                    head_dim=8, d_model=32, d_ff=48, d_expert=16,
                    experts_total=8, experts_held=(0, 8), top_k=2)
        base.update(kw)
        return LFM2Config(**base)


class ShortConv(nn.Module):
    cfg: LFM2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d, K = cfg.d_model, cfg.conv_taps
        bcx = nn.Dense(3 * d, use_bias=False, dtype=cfg.dtype,
                       name="in_proj")(x)
        taps = self.param("taps", nn.initializers.normal(K ** -0.5), (d, K),
                          jnp.float32)
        return nn.Dense(d, use_bias=False, dtype=cfg.dtype,
                        name="out_proj")(gated_short_conv(bcx, taps))


class DenseMLP(nn.Module):
    cfg: LFM2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda width, name: nn.Dense(width, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        return dense(cfg.d_model, "w2")(
            nn.silu(dense(cfg.d_ff, "w1")(x)) * dense(cfg.d_ff, "w3")(x))


class Block(nn.Module):
    """Block ``layer`` of the decoder: its operator from
    ``cfg.layer_types[layer]``, its feed-forward from ``layer <
    cfg.num_dense_layers``. ``select_bias`` (experts_total,) is the routed
    layer's, and None in a dense block or without a bias."""
    cfg: LFM2Config
    layer: int

    @nn.compact
    def __call__(self, x, select_bias=None):
        cfg = self.cfg
        kind = cfg.layer_types[self.layer]
        with _tracing.scope("lfm2/block"):
            u = RMSNorm(cfg.rms_eps, name="norm_op")(x)
            if kind == "conv":
                with _tracing.scope("lfm2/shortconv"):
                    x = x + ShortConv(cfg, name="conv")(u)
            elif kind == "full_attention":
                with _tracing.scope("lfm2/attn"):
                    x = x + GroupedQueryAttention(
                        cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                        cfg.d_model, cfg.dtype, cfg.attention,
                        cfg.flash_blocks, qk_norm_eps=cfg.rms_eps,
                        rope_theta=cfg.rope_theta, name="attn")(u)
            else:
                raise ValueError(f"layer_types[{self.layer}] = {kind!r}: "
                                 "expected 'conv' or 'full_attention'")
            u = RMSNorm(cfg.rms_eps, name="norm_ff")(x)
            if self.layer < cfg.num_dense_layers:
                with _tracing.scope("lfm2/dense_mlp"):
                    return x + DenseMLP(cfg, name="mlp")(u)
            from horovod_tpu.ops.moe import RoutedExperts
            return x + RoutedExperts(
                cfg.experts_total, cfg.experts_held, cfg.top_k, cfg.d_expert,
                cfg.norm_topk, cfg.dtype, cfg.ep_axis, score="sigmoid",
                norm_eps=1e-6, scale=cfg.routed_scale, name="moe")(
                    u, select_bias)


class LFM2(nn.Module):
    """The decoder; see the module's docstring. Parameters: ``wte`` (the
    embedding and, tied, the head that :func:`loss_fn` applies), ``h<i>``
    and ``norm_f``."""
    cfg: LFM2Config

    @nn.compact
    def __call__(self, tokens, expert_bias=None):
        """Hidden states (B, T, d_model) after the final norm.
        ``expert_bias`` (num_layers, experts_total) fp32 is the routers'
        selection bias, a row a layer (the dense layers' rows are not
        read); None routes by the scores alone."""
        cfg = self.cfg
        if len(cfg.layer_types) != cfg.num_layers:
            raise ValueError(
                f"layer_types names {len(cfg.layer_types)} layers, "
                f"num_layers={cfg.num_layers}")
        if expert_bias is not None and not cfg.use_expert_bias:
            raise ValueError("expert_bias given and use_expert_bias=False")
        wte = self.param("wte", nn.initializers.normal(cfg.embed_std),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        x = wte[tokens].astype(cfg.dtype)
        block = remat_block(Block, cfg)
        for i in range(cfg.num_layers):
            routed = i >= cfg.num_dense_layers and expert_bias is not None
            x = block(cfg, i, name=f"h{i}")(
                x, expert_bias[i] if routed else None)
        return RMSNorm(cfg.rms_eps, name="norm_f")(x)


def loss_fn(model: LFM2, params, tokens, expert_bias=None):
    """Mean next-token cross entropy of ``tokens`` (B, T) over the ``T - 1``
    positions of each row that have a next token, through the tied head over
    the rows of the vocabulary held here."""
    hidden = model.apply({"params": params}, tokens, expert_bias)
    with _tracing.scope("lfm2/loss_head"):
        return next_token_cross_entropy(hidden, params["wte"], tokens)
