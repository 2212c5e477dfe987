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

RoPE, RMSNorm and the grouped-query expansion are ``models/llama.py``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu import tracing as _tracing
from horovod_tpu.models.llama import RMSNorm, apply_rope, repeat_kv
from horovod_tpu.models.remat import remat_block

__all__ = ["SmallThinker", "SmallThinkerConfig", "loss_fn"]

_PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Shapes of the decoder and what of it is held here; the defaults are
    SmallThinker-21BA3B-Instruct's published ones with every expert and the
    whole vocabulary held."""
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


class Attention(nn.Module):
    """Grouped-query causal attention of layer ``layer``: rotated or not,
    windowed or not, as the two layouts say."""
    cfg: SmallThinkerConfig
    layer: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, _ = x.shape
        H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        proj = lambda heads, name: nn.Dense(
            heads * hd, use_bias=False, dtype=cfg.dtype,
            name=name)(x).reshape(B, T, heads, hd)
        q, k, v = proj(H, "wq"), proj(Hkv, "wk"), proj(Hkv, "wv")
        if cfg.rope_layout[self.layer]:
            positions = jnp.arange(T)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        k, v = repeat_kv(k, v, H)
        from horovod_tpu.ops.attention import multihead_attention
        o = multihead_attention(
            q, k, v, impl=cfg.attention, causal=True, out_dtype=cfg.dtype,
            flash_blocks=cfg.flash_blocks,
            window=(cfg.sliding_window
                    if cfg.sliding_window_layout[self.layer] else None))
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="wo")(o.reshape(B, T, H * hd))


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
            attn = Attention(cfg, self.layer, name="attn")
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
        if cfg.num_heads % cfg.num_kv_heads:
            raise ValueError(
                f"num_kv_heads={cfg.num_kv_heads} must divide "
                f"num_heads={cfg.num_heads}")
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
        logits = jnp.einsum("btd,vd->btv", hidden.astype(jnp.float32),
                            params["lm_head"])
        # log-sum-exp minus the target's logit (models/lfm2.loss_fn says
        # why); the row's last position has no next token and is left out
        # at the end, on (B, T) values
        target = jnp.take_along_axis(
            logits, jnp.roll(tokens, -1, axis=1)[..., None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - target
        return jnp.mean(nll[:, :-1])
