"""SDAR-style block-diffusion decoder with routed experts.

A decoder as ``models/llama.py`` (RMSNorm, RoPE, grouped-query attention,
SwiGLU, all without bias) with four differences: a head size set apart from
``d_model / heads``, an RMSNorm over every query and key head before RoPE
(QK-norm), a dropless top-k expert layer in every block
(``ops/moe.RoutedExperts``, told which experts it holds), and an untied head
over the rows of the vocabulary held here. It is trained by masked diffusion
over blocks (Arriola et al. 2025, block diffusion; the SDAR family's
``sdar_moe``): each block of ``block_len`` tokens draws a level ``t``, every
token of the block is replaced by the mask id with probability ``t``, and
the row goes through the layers as ``[noisy ; clean]``, 2T positions with
position ids ``0..T-1, 0..T-1``, under the block-diffusion mask
(``ops/attention.block_diffusion_mask``): a noisy block sees itself and the
clean blocks before it, a clean block sees the clean blocks up to itself.
Only the noisy half goes through the head; the loss is the cross entropy of
the masked positions, each weighted ``1 / t`` of its block.

RMSNorm and the grouped-query attention are ``models/layers.py``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu import tracing as _tracing
from horovod_tpu.models.layers import GroupedQueryAttention, RMSNorm
from horovod_tpu.models.remat import remat_block

__all__ = ["SDAR", "SDARConfig", "block_noise", "loss_fn"]


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    """Shapes of the decoder and what of it is held here; the defaults are
    SDAR-30B-A3B-Chat's published ones with every expert and the whole
    vocabulary held."""
    TRAINING_ONLY = ("block-diffusion sampling yields a block a step and "
                     "the expert layer has no decode path")
    vocab_size: int = 151936         # rows of the embedding and head held
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128              # not d_model / num_heads
    d_model: int = 2048
    d_expert: int = 768              # width of one expert's SwiGLU
    experts_total: int = 128         # the router's width
    experts_held: Tuple[int, int] = (0, 128)    # (first, count) held here
    top_k: int = 8
    norm_topk: bool = True
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    block_len: int = 4
    mask_id: Optional[int] = None    # None: the last row held
    t_min: float = 1e-3              # levels are uniform in [t_min, 1]
    embed_std: float = 0.02          # the embedding rows are N(0, embed_std)
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "dense"         # "dense" | "flash"
    flash_blocks: Optional[tuple] = None
    remat: bool = False
    remat_policy: str = "full"       # "full" | "dots" (GPT2Config docs)
    ep_axis: Optional[str] = None    # mesh axis the experts are sharded on

    @property
    def mask_token(self) -> int:
        return self.vocab_size - 1 if self.mask_id is None else self.mask_id

    @staticmethod
    def tiny(**kw) -> "SDARConfig":
        """A size for CPU tests that keeps the kinds of ratio: more query
        heads than key/value heads, a head size apart from ``d_model /
        heads``, several experts a position."""
        base = dict(vocab_size=256, num_layers=2, num_heads=4,
                    num_kv_heads=2, head_dim=16, d_model=32, d_expert=16,
                    experts_total=8, experts_held=(0, 8), top_k=2)
        base.update(kw)
        return SDARConfig(**base)


class Block(nn.Module):
    cfg: SDARConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        from horovod_tpu.ops.moe import RoutedExperts
        with _tracing.scope("sdar/attn"):
            x = x + GroupedQueryAttention(
                cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model,
                cfg.dtype, cfg.attention, cfg.flash_blocks,
                qk_norm_eps=cfg.rms_eps, rope_theta=cfg.rope_theta,
                diffusion_block=cfg.block_len, name="attn")(
                    RMSNorm(cfg.rms_eps, name="norm_attn")(x), positions)
        with _tracing.scope("sdar/block"):
            return x + RoutedExperts(
                cfg.experts_total, cfg.experts_held, cfg.top_k, cfg.d_expert,
                cfg.norm_topk, cfg.dtype, cfg.ep_axis, name="moe")(
                    RMSNorm(cfg.rms_eps, name="norm_mlp")(x))


class SDAR(nn.Module):
    """The decoder; see the module's docstring. Parameters: ``wte``,
    ``lm_head`` (used by :func:`loss_fn`), ``h<i>`` and ``norm_f``."""
    cfg: SDARConfig

    @nn.compact
    def __call__(self, noisy, clean):
        """Hidden states of the noisy half, (B, T, d_model), after the final
        norm: ``noisy`` and ``clean`` (B, T) go through every layer as one
        row of 2T positions. :func:`loss_fn` applies the head."""
        cfg = self.cfg
        B, T = clean.shape
        if noisy.shape != clean.shape or T % cfg.block_len:
            raise ValueError(
                f"noisy {noisy.shape} and clean {clean.shape} must be equal "
                f"and whole blocks of {cfg.block_len}")
        wte = self.param("wte", nn.initializers.normal(cfg.embed_std),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        self.param("lm_head", nn.initializers.normal(0.02),
                   (cfg.vocab_size, cfg.d_model), jnp.float32)
        pos = jnp.concatenate([jnp.arange(T), jnp.arange(T)])
        x = wte[jnp.concatenate([noisy, clean], axis=1)].astype(cfg.dtype)
        block = remat_block(Block, cfg)
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"h{i}")(x, pos)
        return RMSNorm(cfg.rms_eps, name="norm_f")(x[:, :T])


def block_noise(keys, seq_len: int, block_len: int, t_min: float = 1e-3):
    """The noise of block-diffusion training for rows with PRNG ``keys``
    (B,): ``(levels, masked)``. ``levels`` (B, seq_len / block_len) are
    uniform in ``[t_min, 1]``, one a block; ``masked`` (B, seq_len) bool
    marks each token with the probability its block's level gives."""
    def one(key):
        k_level, k_mask = jax.random.split(key)
        levels = jax.random.uniform(k_level, (seq_len // block_len,),
                                    jnp.float32, t_min, 1.0)
        u = jax.random.uniform(k_mask, (seq_len,), jnp.float32)
        return levels, u < jnp.repeat(levels, block_len)
    return jax.vmap(one)(keys)


def loss_fn(model: SDAR, params, tokens, noise):
    """The masked-diffusion loss of ``tokens`` (B, T) under ``noise =
    (levels, masked)`` (:func:`block_noise`): the sum over masked positions
    of the cross entropy of the clean token, each over its block's level,
    over ``B * T``. The head sees the noisy half only."""
    cfg = model.cfg
    levels, masked = noise
    noisy = jnp.where(masked, cfg.mask_token, tokens)
    hidden = model.apply({"params": params}, noisy, tokens)
    with _tracing.scope("sdar/loss_head"):
        logits = jnp.einsum("btd,vd->btv", hidden.astype(jnp.float32),
                            params["lm_head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        weight = masked / jnp.repeat(levels, cfg.block_len, axis=1)
        return -jnp.sum(ll * weight) / tokens.size
