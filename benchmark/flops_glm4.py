"""Operations and bytes the latent-attention configuration needs, from shapes
alone, in ``flops.py``'s convention: a product of ``m x k`` by ``k x n`` is
``2 m k n``; training is three times the forward's products; recomputed
operations (remat, the flash backward's scores) are not counted; causal
attention is counted at half of the ``T x T`` products. What a position
meets:

* in every block, and in the multi-token-prediction module's, latent
  attention's five projections (to the query bottleneck and from it, to the
  key/value latent with the rotated key and from it, the output's) and the
  attention products at the expanded head size (``qk_nope_head_dim +
  qk_rope_head_dim`` for the scores, ``v_head_dim`` for the values; every
  query head has a key/value head of its own);
* the first ``first_k_dense_replace`` feed-forwards are a dense SwiGLU; the
  others, and the module's, are counted at what this chip's share requires:
  the router over its full width, each position's ``num_experts_per_tok``
  choices falling on a held expert with probability ``held /
  router_width``, and the shared expert whole;
* the module's ``eh_proj`` (two hidden sizes to one) and a second pass of
  the untied head over the vocabulary slice.
"""


def _layers(cfg):
    """``(attention, dense, routed, modules)``: the blocks with latent
    attention (the module's among them), with a dense SwiGLU, with routed
    and shared experts, and the multi-token-prediction modules."""
    modules = cfg["num_nextn_predict_layers"]
    dense = cfg["first_k_dense_replace"]
    trunk = cfg["num_hidden_layers"]
    return trunk + modules, dense, trunk - dense + modules, modules


def _heads(cfg):
    """``(heads x (nope + rope), heads x v_head)``."""
    heads = cfg["num_attention_heads"]
    return (heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
            heads * cfg["v_head_dim"])


def attention_params(cfg):
    """Parameters of one block's latent attention."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk, v = _heads(cfg)
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"]
                                             + cfg["v_head_dim"])
            + v * d)


def matmul_params(cfg):
    """Parameters one position meets in a matrix product in the whole
    step: the head over the vocabulary slice once a pass."""
    d = cfg["hidden_size"]
    attention, dense, routed, modules = _layers(cfg)
    width = cfg["deployment"]["router_width"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    share = cfg["n_routed_experts"] / width
    return (attention * attention_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + routed * (d * width
                        + cfg["num_experts_per_tok"] * share * expert
                        + cfg["n_shared_experts"] * expert)
            + modules * 2 * d * d
            + (1 + modules) * cfg["vocab_size"] * d)


def attention_fwd_flops_per_token(cfg, seq_len):
    """QK^T and PV of every attention layer for one token of a ``seq_len``
    row, causal at half: ``2 x heads x head size`` a pair and product,
    ``seq_len / 2`` pairs a token."""
    qk, v = _heads(cfg)
    return _layers(cfg)[0] * seq_len * (qk + v)


def train_flops_per_token(cfg, seq_len):
    """``6 x`` the parameters met plus three times the attention forward."""
    return (6 * matmul_params(cfg)
            + 3 * attention_fwd_flops_per_token(cfg, seq_len))


def flash_train_flops_per_token(cfg, seq_len):
    """What the flash kernels of one training step have to do per token:
    the forward (2 products) and the backward (5: dV, dP, dS->dQ, dS->dK and
    the scores again, which the algorithm requires because the forward keeps
    none), causal at half. The forward run again under remat is not
    counted."""
    return 3.5 * attention_fwd_flops_per_token(cfg, seq_len)


def flash_train_bytes_per_token(cfg, seq_len, itemsize=2):
    """Bytes the same calls must move per token if every operand were read
    and every result written once, every head with its own expanded key and
    value: forward q, k, v in and o out; backward q, k, v, o, do in and dq,
    dk, dv out."""
    qk, v = _heads(cfg)
    fwd = 2 * qk + 2 * v
    bwd = (2 * qk + 3 * v) + (2 * qk + v)
    return _layers(cfg)[0] * (fwd + bwd) * itemsize
