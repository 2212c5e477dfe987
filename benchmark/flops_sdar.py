"""Operations and bytes the SDAR block-diffusion configuration needs, from
shapes alone, in ``flops.py``'s convention: a product of ``m x k`` by ``k x
n`` is ``2 m k n``; training is three times the forward's products;
recomputed operations (remat, the flash backward's scores) are not counted.
Two things differ from a causal decoder:

* a clean token costs two positions in every layer (the row runs as
  ``[noisy ; clean]``) and one in the head (the noisy half only);
* attention is counted over the visible pairs of the block-diffusion mask,
  ``T (T + block_len)`` a row of ``T`` clean tokens: ``T block_len`` between
  the noisy tokens of a block, ``T (T - block_len) / 2`` from noisy queries
  to earlier clean blocks, ``T (T + block_len) / 2`` among the clean ones.

The expert layer is counted at what this chip's share requires: each
position's ``num_experts_per_tok`` choices fall on a held expert with
probability ``held / total``.
"""


def _dims(cfg):
    heads = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return cfg["hidden_size"], heads, kv


def layer_matmul_params(cfg):
    """Parameters one position meets in a matrix product in one layer: the
    four attention projections, the router, and the experts it is expected
    to reach here (three matrices each)."""
    d, heads, kv = _dims(cfg)
    share = cfg["num_experts"] / cfg["deployment"]["router_width"]
    return (2 * d * heads + 2 * d * kv + d * cfg["deployment"]["router_width"]
            + cfg["num_experts_per_tok"] * share
            * 3 * d * cfg["moe_intermediate_size"])


def attention_fwd_flops_per_token(cfg, seq_len):
    """QK^T and PV of every layer for one clean token of a ``seq_len`` row,
    over visible pairs: ``seq_len + block_len`` pairs a token, 2 products of
    ``2 x heads x head_dim`` a pair."""
    _, heads, _ = _dims(cfg)
    pairs = seq_len + cfg["assumed"]["block_length"]
    return cfg["num_hidden_layers"] * 4 * heads * pairs


def train_flops_per_token(cfg, seq_len):
    """Per clean token: ``6 x`` the parameters met (two positions a layer,
    one in the head over the vocabulary slice) plus three times the
    attention forward."""
    d, _, _ = _dims(cfg)
    return (6 * (2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                 + cfg["vocab_size"] * d)
            + 3 * attention_fwd_flops_per_token(cfg, seq_len))


def flash_train_flops_per_token(cfg, seq_len):
    """What the flash kernels of one training step have to do per clean
    token: the forward (2 products) and the backward (5: dV, dP, dS->dQ,
    dS->dK and the scores again, which the algorithm requires because the
    forward keeps none), over visible pairs. The forward run again under
    remat is not counted."""
    return 3.5 * attention_fwd_flops_per_token(cfg, seq_len)


def flash_train_bytes_per_token(cfg, seq_len, itemsize=2):
    """Bytes the same calls must move per clean token (two positions) if
    every operand were read and every result written once, with the
    key/value heads as published (grouped-query: 4 heads serve 32): forward
    q, k, v in and o out; backward q, k, v, o, do in and dq, dk, dv out."""
    _, heads, kv = _dims(cfg)
    fwd = 2 * heads + 2 * kv
    bwd = (3 * heads + 2 * kv) + (heads + 2 * kv)
    return cfg["num_hidden_layers"] * 2 * (fwd + bwd) * itemsize
