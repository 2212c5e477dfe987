"""SmallThinker-style decoder: global attention without positions beside
sliding-window attention with RoPE, a router that reads the block's input
before attention, and routed ReGLU experts in every layer.

Layer ``i`` on the residual stream ``x``, no bias anywhere (the family's
``smallthinker``)::

    route   = top_k(softmax(x W_r))          x as it stands, before any norm
    h       = x + Attn_i(RMSNorm(x))
    out     = h + Experts(RMSNorm(h); route)

* ``Attn_i`` is grouped-query causal attention without QK-norm.
  ``rope_layout[i]`` says whether its queries and keys are rotated (RoPE,
  rotate-half over the whole head) or carry no position at all;
  ``sliding_window_layout[i]`` whether a query sees every key up to its own
  or only the last ``sliding_window`` of them, its own included. The
  published models pair the two: a layer is global and position-free, or
  windowed and rotated.
* ``Experts`` is the dropless routed layer (``ops/moe.RoutedExperts``, told
  which experts it holds) with ReGLU experts, ``down(relu(gate u) * up u)``,
  under the softmax rule: scores over all experts, the top ``top_k``, gates
  renormalised over the chosen. The choice and the gates are made from the
  block's **input** (``route_from``), the rows the experts read are the
  normed stream **after** attention: the router's gradient reaches the
  stream before attention, the rows' after it.

The head is untied, after one more RMSNorm. Training only: the serving cache
has one kind of block table and no window, and the expert layer no decode
path.

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

__all__ = ["Attention", "SmallThinker", "SmallThinkerConfig", "attention",
           "loss_fn"]

_PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Shapes of the decoder and what of it is held here; the defaults are
    SmallThinker-21BA3B-Instruct's published ones with every expert and the
    whole vocabulary held."""
    TRAINING_ONLY = ("the cache has one kind of block table and the decode "
                     "kernels no window (a window layer's blocks would have "
                     "to be a ring of its last sliding_window keys beside a "
                     "global layer's whole row), and the expert layer has "
                     "no decode path")
    vocab_size: int = 151936         # rows of the embedding and of the head
    num_layers: int = 52
    sliding_window_layout: Tuple[int, ...] = _PERIOD * 13   # 1: windowed
    rope_layout: Tuple[int, ...] = _PERIOD * 13             # 1: rotated
    sliding_window: int = 4096       # keys a windowed query sees, with its own
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    d_model: int = 2560
    d_expert: int = 768              # width of one expert's ReGLU
    experts_total: int = 64          # the router's width
    experts_held: Tuple[int, int] = (0, 64)     # (first, count) held here
    top_k: int = 6
    norm_topk: bool = True
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    embed_std: float = 0.02          # the embedding rows are N(0, embed_std)
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "dense"         # "dense" | "flash"
    flash_blocks: Optional[tuple] = None
    remat: bool = False
    remat_policy: str = "full"       # "full" | "dots" (GPT2Config docs)
    ep_axis: Optional[str] = None    # mesh axis the experts are sharded on

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        """A size for CPU tests that keeps the kinds of layer and of ratio:
        one whole period, a window shorter than a row, more query heads than
        key/value heads, several experts a position."""
        base = dict(vocab_size=256, num_layers=4,
                    sliding_window_layout=_PERIOD, rope_layout=_PERIOD,
                    sliding_window=8, num_heads=4, num_kv_heads=2,
                    head_dim=8, d_model=32, d_expert=16, experts_total=8,
                    experts_held=(0, 8), top_k=2)
        base.update(kw)
        return SmallThinkerConfig(**base)


Attention = GroupedQueryAttention      # the class of every h<i>/attn


def attention(cfg: SmallThinkerConfig, layer: int, **kw) -> Attention:
    """Grouped-query causal attention of layer ``layer``: rotated or not,
    windowed or not, as the two layouts say; ``kw`` names it."""
    return Attention(
        cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model,
        cfg.dtype, cfg.attention, cfg.flash_blocks,
        rope_theta=cfg.rope_theta if cfg.rope_layout[layer] else None,
        window=cfg.sliding_window if cfg.sliding_window_layout[layer]
        else None, **kw)


class Block(nn.Module):
    """Block ``layer`` of the decoder: the route from its input, attention
    by the layer's kind, the experts on the normed stream after it."""
    cfg: SmallThinkerConfig
    layer: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        windowed = cfg.sliding_window_layout[self.layer]
        with _tracing.scope("smallthinker/block"):
            u = RMSNorm(cfg.rms_eps, name="norm_in")(x)
            attn = attention(cfg, self.layer, name="attn")
            if windowed:
                with _tracing.scope("smallthinker/attn_window"):
                    h = x + attn(u)
            else:
                with _tracing.scope("smallthinker/attn_global"):
                    h = x + attn(u)
            m = RMSNorm(cfg.rms_eps, name="norm_post")(h)
            from horovod_tpu.ops.moe import RoutedExperts
            return h + RoutedExperts(
                cfg.experts_total, cfg.experts_held, cfg.top_k, cfg.d_expert,
                cfg.norm_topk, cfg.dtype, cfg.ep_axis, score="softmax",
                act="relu", name="moe")(m, route_from=x)


class SmallThinker(nn.Module):
    """The decoder; see the module's docstring. Parameters: ``wte``,
    ``h<i>``, ``norm_f`` and ``lm_head`` (vocabulary x d, the untied head
    that :func:`loss_fn` applies)."""
    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, tokens):
        """Hidden states (B, T, d_model) after the final norm."""
        cfg = self.cfg
        for name in ("sliding_window_layout", "rope_layout"):
            if len(getattr(cfg, name)) != cfg.num_layers:
                raise ValueError(
                    f"{name} names {len(getattr(cfg, name))} layers, "
                    f"num_layers={cfg.num_layers}")
        wte = self.param("wte", nn.initializers.normal(cfg.embed_std),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        self.param("lm_head", nn.initializers.normal(0.02),
                   (cfg.vocab_size, cfg.d_model), jnp.float32)
        x = wte[tokens].astype(cfg.dtype)
        block = remat_block(Block, cfg)
        for i in range(cfg.num_layers):
            x = block(cfg, i, name=f"h{i}")(x)
        return RMSNorm(cfg.rms_eps, name="norm_f")(x)


def loss_fn(model: SmallThinker, params, tokens):
    """Mean next-token cross entropy of ``tokens`` (B, T) over the ``T - 1``
    positions of each row that have a next token, through the untied head
    over the rows of the vocabulary held here."""
    hidden = model.apply({"params": params}, tokens)
    with _tracing.scope("smallthinker/loss_head"):
        return next_token_cross_entropy(hidden, params["lm_head"], tokens)
