"""Llama-family decoder — RoPE + RMSNorm + SwiGLU + grouped-query attention.

Widens the zoo beyond the five BASELINE configs to the architecture users
actually migrate with (upstream Horovod's role here is its framework-native
example models, ``horovod/examples``; the zoo plays that part on TPU). The
TPU-first choices mirror ``gpt2.py``: bf16 compute with fp32 norms and
logits, the shared fused attention op (``ops/attention.py`` /
``ops/flash_attention.py``), ring / Ulysses sequence parallelism on the
same mesh axes, Megatron tensor-parallel partition rules with one psum per
attention/MLP pair, and selective rematerialization policies.

Grouped-query attention is computed by expanding K/V heads to the query
head count (``jnp.repeat`` on the head axis) right before the attention
op: the expansion happens AFTER the kv projections, so the parameter and
optimizer memory savings of GQA are real, while the attention kernels see
plain MHA shapes — one code path for dense, flash, ring, and Ulysses.
XLA turns the repeat into a broadcast inside the fused attention when it
can; the kv-cache-bandwidth win GQA exists for is an inference concern
that doesn't bind a training framework.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.models.gpt2 import loss_fn  # same next-token CE  # noqa: F401
from horovod_tpu.models.gpt2 import loss_fn_moe  # CE + aux  # noqa: F401
from horovod_tpu.models.remat import remat_block
from horovod_tpu.parallel.sharding import PartitionRules


__all__ = ["Llama", "LlamaConfig", "loss_fn", "loss_fn_moe",
           "partition_rules", "apply_rope", "repeat_kv"]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000          # already a 128 multiple
    max_seq_len: int = 2048
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32           # < num_heads = grouped-query attention
    d_model: int = 4096
    d_ff: int = 11008                # SwiGLU hidden width
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6            # HF Llama-2/3 ship 1e-5 (convert.py)
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    remat_policy: str = "full"       # "full" | "dots" (GPT2Config docs)
    use_ring_attention: bool = False
    ring_layout: str = "contiguous"  # "contiguous" | "striped" (gpt2 docs)
    sp_impl: str = "ring"            # "ring" | "ulysses"
    attention: str = "dense"         # "dense" | "flash"
    flash_blocks: Optional[tuple] = None
    # num_experts > 0 swaps every SwiGLU for a Mixtral-style MoE layer:
    # bias-free SwiGLU experts behind a top-2 router (ops/moe.py),
    # experts sharded over the "ep" mesh axis. Add the sown "losses"
    # aux (loss_fn_moe) to the objective.
    num_experts: int = 0
    expert_capacity_factor: float = 1.25
    moe_router: str = "top2"         # Mixtral routes top-2

    @staticmethod
    def llama7b() -> "LlamaConfig":
        return LlamaConfig()         # the defaults ARE 7B

    @staticmethod
    def small() -> "LlamaConfig":
        """~110M-class config for single-chip experiments."""
        return LlamaConfig(num_layers=12, num_heads=12, num_kv_heads=4,
                           d_model=768, d_ff=2048, max_seq_len=1024)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        base = dict(vocab_size=256, max_seq_len=128, num_layers=2,
                    num_heads=4, num_kv_heads=2, d_model=64, d_ff=128)
        base.update(kw)          # overrides of the tiny defaults allowed
        return LlamaConfig(**base)


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


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, deterministic=True):
        cfg = self.cfg
        B, T, D = x.shape
        H, Hkv = cfg.num_heads, cfg.num_kv_heads
        hd = D // H
        q = nn.Dense(H * hd, use_bias=False, dtype=cfg.dtype,
                     name="wq")(x).reshape(B, T, H, hd)
        k = nn.Dense(Hkv * hd, use_bias=False, dtype=cfg.dtype,
                     name="wk")(x).reshape(B, T, Hkv, hd)
        v = nn.Dense(Hkv * hd, use_bias=False, dtype=cfg.dtype,
                     name="wv")(x).reshape(B, T, Hkv, hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        k, v = repeat_kv(k, v, H)    # GQA: expand kv heads to MHA shapes
        from horovod_tpu.ops.attention import sp_attention
        o = sp_attention(q, k, v, cfg, segment_ids=segment_ids)
        return nn.Dense(D, use_bias=False, dtype=cfg.dtype,
                        name="wo")(o.reshape(B, T, D))


class SwiGLU(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if cfg.num_experts > 0:
            # Mixtral recipe: SwiGLU experts + top-2 routing; same
            # dispatch/combine einsums as the GPT-2 MoE path, so GSPMD
            # derives the identical ep all-to-alls.
            from horovod_tpu.ops.moe import MoEMLP
            out, aux = MoEMLP(cfg.num_experts, cfg.d_ff,
                              cfg.expert_capacity_factor, cfg.dtype,
                              router_type=cfg.moe_router,
                              activation="swiglu", name="moe")(x)
            self.sow("losses", "moe_aux", aux)
            return out
        g = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                     name="gate")(x)
        u = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                     name="up")(x)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="down")(nn.silu(g) * u)


class Block(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, deterministic=True):
        cfg = self.cfg
        x = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, name="norm_attn")(x), positions,
            segment_ids,
            deterministic)
        x = x + SwiGLU(cfg, name="mlp")(
            RMSNorm(cfg.rms_eps, name="norm_mlp")(x))
        return x


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 segment_ids=None, positions=None):
        """``segment_ids`` (B, T) int enables sequence packing (see
        GPT2.__call__): cross-document attention is blocked and RoPE
        angles restart per document. ``positions`` overrides the RoPE
        position ids (required for packed sp shards)."""
        cfg = self.cfg
        if cfg.num_heads % cfg.num_kv_heads:
            raise ValueError(
                f"num_kv_heads={cfg.num_kv_heads} must divide "
                f"num_heads={cfg.num_heads}")
        from horovod_tpu.ops.attention import (packed_positions,
                                               sp_global_positions,
                                               validate_sp_config)
        validate_sp_config(cfg)
        B, T = tokens.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
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
            # Global positions for this sp shard feed RoPE's explicit
            # position input (the same role as gpt2's wpe indexing).
            pos = sp_global_positions(T, cfg)
        x = wte[tokens].astype(cfg.dtype)
        block = remat_block(Block, cfg, static_argnums=(4,))
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"h{i}")(x, pos, segment_ids,
                                         deterministic)
        x = RMSNorm(cfg.rms_eps, name="norm_f")(x)
        # Untied lm head (Llama convention), fp32 logits.
        wlm = self.param("lm_head", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        return jnp.einsum("btd,vd->btv", x.astype(jnp.float32), wlm)


def partition_rules() -> PartitionRules:
    """Megatron tp sharding (SURVEY §2 row 26): column-parallel q/k/v and
    gate/up (shard output features), row-parallel wo/down (shard input
    features) — one psum per attention/MLP pair under GSPMD; embeddings
    and lm head shard the vocab axis."""
    return PartitionRules([
        (r"wte$", P("tp", None)),
        (r"lm_head$", P("tp", None)),
        (r"(wq|wk|wv|gate|up)/kernel$", P(None, "tp")),
        (r"(wo|down)/kernel$", P("tp", None)),
        (r"moe/(w_gate|w_in|w_out)$", P("ep", None, None)),
        (r"scale$", P()),
    ])
