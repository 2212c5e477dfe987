"""BERT (reference benchmark config: "BERT-large pretraining, TF2
DistributedGradientTape + Adasum") — flax encoder with MLM + NSP heads.

TPU-first: vocab padded to a 128 multiple, bf16 matmuls with fp32
layernorm/softmax/logits, fused qkv projection (one MXU matmul instead of
three), optional remat per layer.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.remat import remat_block


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30592          # 30522 padded up to a 128 multiple
    max_seq_len: int = 512
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    type_vocab_size: int = 2
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    # "full" | "dots" (see GPT2Config.remat_policy): "dots" saves MXU
    # outputs and recomputes only elementwise/norm work in backward.
    remat_policy: str = "full"
    # "dense" | "flash" (fused pallas kernel; the key-padding mask rides the
    # kernel's key_bias input).
    attention: str = "dense"
    # Optional (block_q, block_k) flash tiling override (autotuned).
    flash_blocks: Optional[tuple] = None
    # Sequence parallelism for long-context encoding (non-causal ring /
    # ulysses over an "sp" mesh axis; same dispatch as GPT-2/Llama).
    # Key-padding masks ride every path: the rings rotate the shard's
    # mask with its k/v block, ulysses allgathers the bool. Under sp the
    # mask is this shard's (batch, t_local) slice, sharded like tokens.
    use_ring_attention: bool = False
    sp_impl: str = "ring"            # "ring" | "ulysses"
    ring_layout: str = "contiguous"  # "contiguous" | "striped"

    @staticmethod
    def large() -> "BertConfig":
        return BertConfig(num_layers=24, num_heads=16, d_model=1024)

    @staticmethod
    def tiny() -> "BertConfig":
        return BertConfig(vocab_size=256, max_seq_len=64, num_layers=2,
                          num_heads=4, d_model=64)


class EncoderLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask, segment_ids=None):
        cfg = self.cfg
        B, T, D = x.shape
        H = cfg.num_heads
        qkv = nn.Dense(3 * D, dtype=cfg.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, D // H)
        k = k.reshape(B, T, H, D // H)
        v = v.reshape(B, T, H, D // H)
        if cfg.use_ring_attention:
            # Long-context sp through the shared non-causal dispatch; the
            # shard's key-padding mask / packing ids ride every path (the
            # rings rotate them with k/v, ulysses allgathers them).
            from horovod_tpu.ops.attention import sp_attention
            att = sp_attention(q, k, v, cfg, causal=False, key_mask=mask,
                               segment_ids=segment_ids).reshape(B, T, D)
        else:
            from horovod_tpu.ops.attention import multihead_attention
            att = multihead_attention(q, k, v, impl=cfg.attention,
                                      causal=False, key_mask=mask,
                                      segment_ids=segment_ids,
                                      out_dtype=cfg.dtype,
                                      flash_blocks=cfg.flash_blocks
                                      ).reshape(B, T, D)
        att = nn.Dense(D, dtype=cfg.dtype, name="out")(att)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_att")(x + att)
        h = nn.Dense(4 * D, dtype=cfg.dtype, name="fc")(x)
        h = nn.gelu(h)
        h = nn.Dense(D, dtype=cfg.dtype, name="proj")(h)
        return nn.LayerNorm(dtype=jnp.float32, name="ln_mlp")(x + h)


class Bert(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, tokens, token_types=None, attention_mask=None,
                 segment_ids=None, positions=None):
        """``segment_ids`` (B, T) int enables sequence packing (packed
        MLM pretraining): attention blocked across document boundaries,
        wpe rows restart per document unless explicit ``positions`` are
        given (required under packed sp). Note: upstream-BERT "segment
        A/B" embeddings are ``token_types`` — a different thing."""
        cfg = self.cfg
        from horovod_tpu.ops.attention import (packed_positions,
                                               sp_global_positions,
                                               validate_sp_config)
        validate_sp_config(cfg)
        B, T = tokens.shape
        if token_types is None:
            token_types = jnp.zeros_like(tokens)
        if attention_mask is None and not cfg.use_ring_attention:
            attention_mask = jnp.ones((B, T), bool)
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.02),
                         (cfg.max_seq_len, cfg.d_model), jnp.float32)
        wtt = self.param("wtt", nn.initializers.normal(0.02),
                         (cfg.type_vocab_size, cfg.d_model), jnp.float32)
        if positions is not None:
            pos = positions
        elif segment_ids is not None:
            if cfg.use_ring_attention:
                raise ValueError(
                    "packed sequences under sp need explicit positions= "
                    "(per-shard pos-in-segment; the shard cannot see "
                    "where its documents started)")
            pos = packed_positions(segment_ids)          # (B, T)
        else:
            # Under sp, wpe follows this shard's *global* positions.
            pos = sp_global_positions(T, cfg)
        pe = wpe[pos]
        if pe.ndim == 2:          # (T, D): shared positions, broadcast B
            pe = pe[None]
        x = (wte[tokens] + pe + wtt[token_types]).astype(cfg.dtype)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_emb")(x)
        layer = remat_block(EncoderLayer, cfg)
        for i in range(cfg.num_layers):
            x = layer(cfg, name=f"layer{i}")(x, attention_mask,
                                             segment_ids)
        # MLM head: tied embeddings, fp32 logits (per-shard rows under sp).
        mlm = jnp.einsum("btd,vd->btv", x.astype(jnp.float32), wte)
        # NSP head on [CLS]. Under sp, global position 0 lives on shard 0
        # in BOTH layouts (contiguous: rank-major; striped: pos = r + n*i);
        # replicate it to every shard so the head computes identically.
        cls = x[:, 0]
        if cfg.use_ring_attention:
            r = jax.lax.axis_index("sp")
            cls = jax.lax.psum(
                jnp.where(r == 0, cls, jnp.zeros_like(cls)), "sp")
        pooled = nn.tanh(nn.Dense(cfg.d_model, dtype=jnp.float32,
                                  name="pooler")(cls.astype(jnp.float32)))
        nsp = nn.Dense(2, dtype=jnp.float32, name="nsp")(pooled)
        return mlm, nsp


def mlm_loss(mlm_logits, tokens, mask_positions):
    """Masked-LM cross entropy over masked positions (0/1 mask)."""
    logp = jax.nn.log_softmax(mlm_logits, axis=-1)
    ll = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask_positions.sum(), 1)
    return -(ll * mask_positions).sum() / denom
