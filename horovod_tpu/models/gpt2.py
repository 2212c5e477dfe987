"""GPT-2 (reference benchmark config: "GPT-2 medium, torch-xla backend,
tensor-fusion stress") — flax implementation designed for dp x tp x sp
sharding from the start.

TPU-first choices: vocab padded to a multiple of 128 (MXU tiling), bf16
matmuls with fp32 layernorm/softmax/logits, explicit qkv/out + fc/proj
parameter names so ``partition_rules`` can shard them Megatron-style
(column-parallel then row-parallel — XLA inserts the single psum per block
that Megatron does by hand), optional ``jax.checkpoint`` per block to trade
FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

from horovod_tpu import tracing as _tracing
from horovod_tpu.models.remat import remat_block
from horovod_tpu.parallel.sharding import PartitionRules



@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304          # 50257 padded up to a 128 multiple
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    dropout: float = 0.0
    ln_eps: float = 1e-6             # HF checkpoints use 1e-5 (convert.py)
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    # Rematerialization policy when remat=True (models/remat.py). "full"
    # recomputes the whole block in backward (minimum memory, ~33% extra
    # FLOPs, the flash forward kernel run twice). "dots" SAVES what the MXU
    # produced and recomputes only the cheap elementwise/norm work: the
    # weight products (jax.checkpoint_policies.
    # dots_with_no_batch_dims_saveable: qkv/out/fc/proj) and, with
    # attention="flash", the kernel's output and row log-sum-exp, which its
    # forward rule names (flash_out, flash_lse). In bytes a layer at
    # B x T x d_model in bf16: 9 x B*T*d for the products (151 MB at
    # 8 x 1024 x 1024) and 1 x B*T*d (17 MB) + B*H*T fp32 for the kernel's —
    # the lever for trading HBM back for recompute when the batch fits.
    remat_policy: str = "full"
    use_ring_attention: bool = False  # sequence-parallel attention (ops/)
    # "contiguous" | "striped": how sequence positions map to sp shards.
    # Striped (Striped Attention) balances causal ring work and lets
    # striped_lm_loss cover every token pair exactly; feed tokens striped:
    # shard r holds positions r, r+n, r+2n, ...
    ring_layout: str = "contiguous"
    # "ring" | "ulysses": sequence-parallel mechanism. Ring hops K/V blocks
    # device-to-device (ppermute; composes with ring_layout); Ulysses
    # all-to-alls heads<->sequence so each device runs ordinary full-
    # sequence attention on a head subset (contiguous layout only).
    sp_impl: str = "ring"
    # "dense" | "flash" (fused pallas kernel, single-device/dp layouts).
    attention: str = "dense"
    # Optional (block_q, block_k) flash tiling override; feed
    # autotune_flash_blocks' pick for this shape, None = kernel defaults.
    flash_blocks: Optional[tuple] = None
    # > 0 replaces every block's dense MLP with an expert-parallel MoE MLP
    # (ops/moe.py); experts shard over the "ep" mesh axis. Aux load-balance
    # losses are sown into the "losses" collection — train with
    # mutable=["losses"] and add their mean (see examples / loss_fn_moe).
    num_experts: int = 0
    expert_capacity_factor: float = 1.25
    moe_router: str = "top1"   # "top1" (Switch) | "top2" (GShard)

    @staticmethod
    def medium() -> "GPT2Config":
        return GPT2Config(num_layers=24, num_heads=16, d_model=1024)

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        return GPT2Config(vocab_size=256, max_seq_len=128, num_layers=2,
                          num_heads=4, d_model=64, **kw)


class Attention(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, segment_ids=None, deterministic=True):
        cfg = self.cfg
        B, T, D = x.shape
        H = cfg.num_heads
        qkv = nn.Dense(3 * D, dtype=cfg.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, D // H)
        k = k.reshape(B, T, H, D // H)
        v = v.reshape(B, T, H, D // H)
        from horovod_tpu.ops.attention import sp_attention
        o = sp_attention(q, k, v, cfg, segment_ids=segment_ids)
        o = o.reshape(B, T, D)
        return nn.Dense(D, dtype=cfg.dtype, name="out")(o)


class MLP(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.cfg
        if cfg.num_experts > 0:
            from horovod_tpu.ops.moe import MoEMLP
            out, aux = MoEMLP(cfg.num_experts, 4 * cfg.d_model,
                              cfg.expert_capacity_factor, cfg.dtype,
                              router_type=cfg.moe_router, name="moe")(x)
            self.sow("losses", "moe_aux", aux)
            return out
        h = nn.Dense(4 * cfg.d_model, dtype=cfg.dtype, name="fc")(x)
        h = nn.gelu(h)
        return nn.Dense(cfg.d_model, dtype=cfg.dtype, name="proj")(h)


class Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, segment_ids=None, deterministic=True):
        cfg = self.cfg
        with _tracing.scope("gpt2/attn"):
            ln1 = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=jnp.float32,
                               name="ln1")(x)
            x = x + Attention(cfg, name="attn")(ln1, segment_ids,
                                                deterministic)
        with _tracing.scope("gpt2/mlp"):
            ln2 = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=jnp.float32,
                               name="ln2")(x)
            x = x + MLP(cfg, name="mlp")(ln2, deterministic)
        return x


class GPT2(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 segment_ids=None, positions=None):
        """``segment_ids`` (B, T) int enables sequence packing: attention
        is blocked across document boundaries and (by default) wpe rows
        restart per document. ``positions`` overrides the position ids
        (required for packed sp shards, where pos-in-segment needs the
        global view the shard doesn't have)."""
        cfg = self.cfg
        from horovod_tpu.ops.attention import (packed_positions,
                                               sp_global_positions,
                                               validate_sp_config)
        validate_sp_config(cfg)
        B, T = tokens.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.max_seq_len, cfg.d_model), jnp.float32)
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
            # Sequence-parallel: wpe is indexed with this shard's
            # *global* positions.
            pos = sp_global_positions(T, cfg)
        x = wte[tokens].astype(cfg.dtype) + wpe[pos].astype(cfg.dtype)
        block = remat_block(Block, cfg, static_argnums=(3,))
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"h{i}")(x, segment_ids, deterministic)
        with _tracing.scope("gpt2/lm_head"):
            x = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=jnp.float32,
                             name="ln_f")(x)
            # Tied lm head in fp32 (logits precision matters for loss).
            return jnp.einsum("btd,vd->btv", x.astype(jnp.float32), wte)


def partition_rules() -> PartitionRules:
    """Megatron-style tp sharding + dp batch sharding (SURVEY §2 row 26).

    Column-parallel qkv/fc (shard output features), row-parallel out/proj
    (shard input features) — under GSPMD this yields exactly one psum per
    attention/MLP pair, same comm volume as hand-written Megatron.
    """
    return PartitionRules([
        (r"wte$", P("tp", None)),
        (r"wpe$", P()),
        (r"attn/qkv/kernel", P(None, "tp")),
        (r"attn/out/kernel", P("tp", None)),
        (r"mlp/fc/kernel", P(None, "tp")),
        (r"mlp/proj/kernel", P("tp", None)),
        (r"attn/qkv/bias", P("tp")),
        (r"mlp/fc/bias", P("tp")),
        # MoE experts shard over ep (GShard-style); router stays replicated.
        (r"moe/(w_in|w_out)$", P("ep", None, None)),
        (r"moe/(b_in|b_out)$", P("ep", None)),
        (r"moe/router/router$", P()),
        (r"(ln1|ln2|ln_f)/(scale|bias)", P()),
    ])


@_tracing.scope("gpt2/loss_head")
def loss_fn(logits: jnp.ndarray, tokens: jnp.ndarray,
            segment_ids: jnp.ndarray = None) -> jnp.ndarray:
    """Next-token cross entropy. With ``segment_ids`` (sequence packing),
    targets that cross a document boundary (the last token of each packed
    document predicting the next document's first) are excluded."""
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if segment_ids is None:
        return -jnp.mean(ll)
    w = (segment_ids[:, 1:] == segment_ids[:, :-1]).astype(ll.dtype)
    return -(ll * w).sum() / jnp.maximum(w.sum(), 1)


def striped_lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray,
                    axis_name: str = "sp") -> jnp.ndarray:
    """Next-token cross entropy for the striped sp layout — **exact** over
    the full sequence (call inside shard_map).

    With striping, local position ``j`` on shard ``r`` is global position
    ``r + n*j``, whose target (global ``r + n*j + 1``) lives at local ``j``
    of shard ``r+1`` — except the last shard, whose targets are shard 0's
    tokens shifted one step. One ``ppermute`` therefore fetches every
    cross-shard target, and all ``T_global - 1`` prediction pairs are
    covered — the contiguous per-shard shift drops the shard-boundary
    pairs. Returns the replicated global mean loss.
    """
    n = lax.psum(1, axis_name)
    r = lax.axis_index(axis_name)
    B, T = tokens.shape
    recv = lax.ppermute(tokens, axis_name,
                        [(i, (i - 1) % n) for i in range(n)])
    shifted = jnp.concatenate([recv[:, 1:], recv[:, :1]], axis=1)
    targets = jnp.where(r == n - 1, shifted, recv)
    # The final global position (last shard, last local slot) predicts
    # nothing.
    valid = jnp.where(r == n - 1,
                      (jnp.arange(T) < T - 1)[None, :],
                      jnp.ones((1, T), bool))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    s = jnp.sum(jnp.where(valid, ll, 0.0))
    c = jnp.sum(jnp.where(valid, jnp.ones_like(ll), 0.0))
    return -lax.psum(s, axis_name) / lax.psum(c, axis_name)


def loss_fn_moe(model: "GPT2", params, tokens: jnp.ndarray,
                aux_weight: float = 1e-2) -> jnp.ndarray:
    """Cross entropy + Switch aux load-balance loss for MoE configs."""
    if model.cfg.num_experts <= 0:
        raise ValueError("loss_fn_moe needs an MoE config "
                         f"(num_experts={model.cfg.num_experts}); use "
                         "loss_fn for dense models")
    logits, state = model.apply({"params": params}, tokens,
                                mutable=["losses"])
    aux = jnp.mean(jnp.stack(jax.tree_util.tree_leaves(state["losses"])))
    return loss_fn(logits, tokens) + aux_weight * aux
