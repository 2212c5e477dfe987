"""T5 encoder-decoder — relative position buckets, RMSNorm, gated-GELU.

Completes the zoo's architecture coverage (decoder-only GPT-2/Llama,
encoder-only BERT, now encoder-decoder; upstream Horovod's role here is
its framework-native example models, ``horovod/examples``). TPU-first
choices mirror the rest of the zoo: bf16 compute with fp32 norms and
logits, static shapes, one module tree GSPMD shards via Megatron
partition rules.

Attention routes through the SHARED dense dispatch
(``ops/attention.multihead_attention`` with ``bias=``/``scale=``): T5's
signature per-head relative position bias is a full ``(H, T_q, T_kv)``
tensor added to the scores, which the pallas flash kernel cannot express
(its fused bias is per-key — see ``ops/flash_attention.py``
``key_bias``). At T5's classic sequence lengths (<= 1k) dense attention
is a small fraction of step time; the long-context/sp machinery stays
with the decoder-only family.

T5 details kept faithfully: no ``1/sqrt(d)`` score scaling (folded into
the initializer in the original), bias-free Dense everywhere, RMSNorm
(shared with Llama), the relative-position bucketing scheme (half exact,
half logarithmic), ONE learned bias table per stack shared across its
layers, cross-attention without any position bias, and the v1.1 recipe
choices (gated-GELU FFN, untied lm head).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.models.llama import RMSNorm
from horovod_tpu.models.remat import remat_block
from horovod_tpu.parallel.sharding import PartitionRules


__all__ = ["T5", "T5Config", "relative_position_bucket", "seq2seq_loss",
           "partition_rules"]


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 512
    d_ff: int = 1024                 # gated-GELU hidden width
    num_heads: int = 8
    head_dim: int = 64               # decoupled from d_model (T5 trait)
    num_encoder_layers: int = 6
    num_decoder_layers: int = 6
    rel_buckets: int = 32
    rel_max_distance: int = 128
    ln_eps: float = 1e-6             # RMSNorm epsilon (HF:
                                     # layer_norm_epsilon, 1e-6 in every
                                     # published T5 recipe)
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    remat_policy: str = "full"       # "full" | "dots" (GPT2Config docs)
    pad_id: int = 0                  # also the decoder start token (T5)

    @staticmethod
    def small() -> "T5Config":
        return T5Config()            # the defaults ARE t5-small class

    @staticmethod
    def tiny(**kw) -> "T5Config":
        base = dict(vocab_size=256, d_model=64, d_ff=128, num_heads=4,
                    head_dim=16, num_encoder_layers=2,
                    num_decoder_layers=2, rel_buckets=8,
                    rel_max_distance=32)
        base.update(kw)
        return T5Config(**base)


def relative_position_bucket(rel_pos: jnp.ndarray, *, bidirectional: bool,
                             num_buckets: int, max_distance: int
                             ) -> jnp.ndarray:
    """T5's bucketing of signed relative positions (key_pos - query_pos).

    Half the buckets cover exact small distances, the other half grow
    logarithmically out to ``max_distance`` (beyond which everything
    shares the last bucket). Bidirectional (encoder) splits the space
    between positive and negative offsets; causal (decoder) only ever
    sees ``rel <= 0`` and maps the future to bucket 0.
    """
    ret = jnp.zeros_like(rel_pos)
    n = num_buckets
    if bidirectional:
        n //= 2
        ret = ret + (rel_pos > 0).astype(jnp.int32) * n
        rel = jnp.abs(rel_pos)
    else:
        rel = jnp.maximum(-rel_pos, 0)
    max_exact = n // 2
    is_small = rel < max_exact
    # log-spaced buckets for larger distances, saturating at n - 1
    relf = jnp.maximum(rel.astype(jnp.float32), 1.0)
    large = max_exact + (
        jnp.log(relf / max_exact)
        / jnp.log(max_distance / max_exact) * (n - max_exact)
    ).astype(jnp.int32)
    large = jnp.minimum(large, n - 1)
    return ret + jnp.where(is_small, rel, large)


class RelativeBias(nn.Module):
    """Learned per-head bias over relative-position buckets; ONE table
    per stack, computed once and shared by all its layers."""
    cfg: T5Config
    bidirectional: bool

    @nn.compact
    def __call__(self, t_q: int, t_kv: int) -> jnp.ndarray:
        cfg = self.cfg
        table = self.param("rel_bias", nn.initializers.normal(0.02),
                           (cfg.rel_buckets, cfg.num_heads), jnp.float32)
        rel = (jnp.arange(t_kv)[None, :] - jnp.arange(t_q)[:, None])
        buckets = relative_position_bucket(
            rel, bidirectional=self.bidirectional,
            num_buckets=cfg.rel_buckets,
            max_distance=cfg.rel_max_distance)
        return table[buckets].transpose(2, 0, 1)      # (H, Tq, Tkv)


class T5Attention(nn.Module):
    """Projections around the SHARED dense attention dispatch
    (``ops/attention.multihead_attention`` with the T5 specifics: a
    per-head additive bias and ``scale=1.0``) — one dense softmax
    implementation in the repo, including its fully-masked-row zeroing
    (an all-padding source row yields zeros, not softmax-over--inf
    garbage).

    ``kv`` defaults to ``x`` (self-attention); pass the encoder output
    for cross-attention. ``key_mask`` (B, Tkv) masks padding keys;
    ``causal`` adds the autoregressive mask.
    """
    cfg: T5Config

    @nn.compact
    def __call__(self, x, kv=None, bias=None, key_mask=None,
                 causal: bool = False):
        from horovod_tpu.ops.attention import multihead_attention
        cfg = self.cfg
        kv = x if kv is None else kv
        B, Tq, _ = x.shape
        Tk = kv.shape[1]
        H, hd = cfg.num_heads, cfg.head_dim
        q = nn.Dense(H * hd, use_bias=False, dtype=cfg.dtype,
                     name="q")(x).reshape(B, Tq, H, hd)
        k = nn.Dense(H * hd, use_bias=False, dtype=cfg.dtype,
                     name="k")(kv).reshape(B, Tk, H, hd)
        v = nn.Dense(H * hd, use_bias=False, dtype=cfg.dtype,
                     name="v")(kv).reshape(B, Tk, H, hd)
        o = multihead_attention(q, k, v, impl="dense", causal=causal,
                                key_mask=key_mask, bias=bias, scale=1.0,
                                out_dtype=cfg.dtype)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="o")(o.reshape(B, Tq, H * hd))


class GatedGelu(nn.Module):
    """t5.1.1 FFN: ``wo(gelu(wi_0(x)) * wi_1(x))``, bias-free."""
    cfg: T5Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        g = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                     name="wi_0")(x)
        u = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                     name="wi_1")(x)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="wo")(nn.gelu(g) * u)


class EncoderLayer(nn.Module):
    cfg: T5Config

    @nn.compact
    def __call__(self, x, bias, key_mask):
        cfg = self.cfg
        x = x + T5Attention(cfg, name="attn")(
            RMSNorm(eps=cfg.ln_eps, name="ln1")(x), bias=bias,
            key_mask=key_mask)
        return x + GatedGelu(cfg, name="mlp")(
            RMSNorm(eps=cfg.ln_eps, name="ln2")(x))


class DecoderLayer(nn.Module):
    cfg: T5Config

    @nn.compact
    def __call__(self, x, enc, bias, enc_mask):
        cfg = self.cfg
        x = x + T5Attention(cfg, name="self_attn")(
            RMSNorm(eps=cfg.ln_eps, name="ln1")(x), bias=bias,
            causal=True)
        # Cross-attention carries NO position bias in T5.
        x = x + T5Attention(cfg, name="cross_attn")(
            RMSNorm(eps=cfg.ln_eps, name="ln2")(x), kv=enc,
            key_mask=enc_mask)
        return x + GatedGelu(cfg, name="mlp")(
            RMSNorm(eps=cfg.ln_eps, name="ln3")(x))


class T5(nn.Module):
    cfg: T5Config

    @nn.compact
    def __call__(self, enc_tokens: jnp.ndarray,
                 dec_tokens: Optional[jnp.ndarray] = None,
                 enc_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """``enc_tokens`` (B, T_enc) source ids, ``dec_tokens`` (B, T_dec)
        decoder INPUT ids (already shifted right — :func:`seq2seq_loss`
        shifts for you). ``enc_mask`` (B, T_enc) bool marks real (non-pad)
        source tokens; defaults to ``enc_tokens != pad_id``. Returns
        fp32 logits (B, T_dec, vocab).

        ``dec_tokens=None`` runs the ENCODER ONLY and returns its
        ``(B, T_enc, d_model)`` states — seq2seq decoding encodes once
        this way and loops the decoder against cached K/V
        (``models/generate.t5_generate``), reusing the shared attention
        dispatch (masked-row zeroing included) instead of
        re-implementing the encoder.
        """
        cfg = self.cfg
        if enc_mask is None:
            enc_mask = enc_tokens != cfg.pad_id
        emb = self.param("embedding", nn.initializers.normal(1.0),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)

        enc_layer = remat_block(EncoderLayer, cfg)
        dec_layer = remat_block(DecoderLayer, cfg)

        # Encoder: bidirectional rel bias, one table for the stack.
        x = emb[enc_tokens].astype(cfg.dtype)
        enc_bias = RelativeBias(cfg, bidirectional=True,
                                name="enc_rel")(x.shape[1], x.shape[1])
        for i in range(cfg.num_encoder_layers):
            x = enc_layer(cfg, name=f"enc{i}")(x, enc_bias, enc_mask)
        enc_out = RMSNorm(eps=cfg.ln_eps, name="enc_norm")(x)
        if dec_tokens is None:
            return enc_out

        # Decoder: causal rel bias (own table), cross-attn without bias.
        y = emb[dec_tokens].astype(cfg.dtype)
        dec_bias = RelativeBias(cfg, bidirectional=False,
                                name="dec_rel")(y.shape[1], y.shape[1])
        for i in range(cfg.num_decoder_layers):
            y = dec_layer(cfg, name=f"dec{i}")(y, enc_out, dec_bias,
                                               enc_mask)
        y = RMSNorm(eps=cfg.ln_eps, name="dec_norm")(y)
        # v1.1: untied lm head, fp32 logits.
        wlm = self.param("lm_head", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        return jnp.einsum("btd,vd->btv", y.astype(jnp.float32), wlm)


def shift_right(tokens: jnp.ndarray, start_id: int) -> jnp.ndarray:
    """Teacher forcing input: prepend the start token, drop the last."""
    return jnp.concatenate(
        [jnp.full_like(tokens[:, :1], start_id), tokens[:, :-1]], axis=1)


def seq2seq_loss(model: "T5", params, enc_tokens: jnp.ndarray,
                 labels: jnp.ndarray,
                 enc_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Teacher-forced cross entropy over non-pad label positions.

    ``labels`` (B, T_dec) are the TARGET ids; the decoder input is their
    right-shift with the pad/start token (T5 uses pad as BOS). Pad label
    positions carry zero weight.
    """
    cfg = model.cfg
    dec_in = shift_right(labels, cfg.pad_id)
    logits = model.apply({"params": params}, enc_tokens, dec_in,
                         enc_mask=enc_mask)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    w = (labels != cfg.pad_id).astype(ll.dtype)
    return -(ll * w).sum() / jnp.maximum(w.sum(), 1)


def partition_rules() -> PartitionRules:
    """Megatron tp sharding, same shape as the llama rules: column-split
    q/k/v and wi, row-split o/wo, vocab-split embedding/lm head,
    replicated norms and the tiny bias tables."""
    return PartitionRules([
        (r"embedding$", P("tp", None)),
        (r"lm_head$", P("tp", None)),
        (r"(q|k|v|wi_0|wi_1)/kernel$", P(None, "tp")),
        (r"(o|wo)/kernel$", P("tp", None)),
        (r"rel_bias$", P()),
        (r"scale$", P()),
    ])
