"""Operations and bytes the LFM2 hybrid configuration needs, from shapes
alone, in ``flops.py``'s convention: a product of ``m x k`` by ``k x n`` is
``2 m k n``; training is three times the forward's products; recomputed
operations (remat, the flash backward's scores) are not counted; causal
attention is counted at half of the ``T x T`` products. What differs by
layer:

* a ``conv`` layer's operator is two projections (d -> 3d, d -> d); the
  gates and the taps between them are element-wise work, a handful of
  multiply-adds a channel, and are not counted as operations (their cost is
  bytes: :func:`short_conv_bytes_per_token`);
* a ``full_attention`` layer's is four projections with the key/value heads
  as published (8 serve 32) and the attention products;
* the first ``num_dense_layers`` feed-forwards are a dense SwiGLU; the others
  are counted at what this chip's share requires: each position's
  ``num_experts_per_tok`` choices fall on a held expert with probability
  ``held / router_width``, plus the router over its full width.
"""


def _dims(cfg):
    """``(d, query width, key/value width)``; the config gives no head size
    of its own: it is ``hidden_size / num_attention_heads``."""
    d = cfg["hidden_size"]
    head = d // cfg["num_attention_heads"]
    return d, d, cfg["num_key_value_heads"] * head


def _layers(cfg):
    """``(conv, attention, dense, routed)`` layers of the configuration."""
    kinds = cfg["layer_types"]
    dense = cfg["num_dense_layers"]
    return (kinds.count("conv"), kinds.count("full_attention"), dense,
            cfg["num_hidden_layers"] - dense)


def matmul_params(cfg):
    """Parameters one position meets in a matrix product in the whole
    model, the tied head over the vocabulary slice included."""
    d, heads, kv = _dims(cfg)
    conv, attention, dense, routed = _layers(cfg)
    width = cfg["deployment"]["router_width"]
    share = cfg["num_experts"] / width
    return (conv * 4 * d * d
            + attention * (2 * d * heads + 2 * d * kv)
            + dense * 3 * d * cfg["intermediate_size"]
            + routed * (d * width + cfg["num_experts_per_tok"] * share
                        * 3 * d * cfg["moe_intermediate_size"])
            + cfg["vocab_size"] * d)


def attention_fwd_flops_per_token(cfg, seq_len):
    """QK^T and PV of every attention layer for one token of a ``seq_len``
    row, causal at half: 2 products of ``2 x heads x head_dim`` a pair,
    ``seq_len / 2`` pairs a token."""
    _, heads, _ = _dims(cfg)
    return _layers(cfg)[1] * 2 * seq_len * heads


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
    and every result written once, with the key/value heads as published
    (grouped-query: 8 heads serve 32): forward q, k, v in and o out; backward
    q, k, v, o, do in and dq, dk, dv out."""
    _, heads, kv = _dims(cfg)
    fwd = 2 * heads + 2 * kv
    bwd = (3 * heads + 2 * kv) + (heads + 2 * kv)
    return _layers(cfg)[1] * (fwd + bwd) * itemsize


def short_conv_bytes_per_token(cfg, itemsize=2):
    """Bytes the gated short convolutions of one training step must move per
    token if every operand were read and every result written once: forward
    the projection's ``3 d`` in and ``d`` out; backward the ``3 d`` and the
    ``d`` of the cotangent in, ``3 d`` out (the taps and their gradient are
    ``d x K`` a layer, nothing a token). The forward run again under remat
    is not counted. No metric reads this yet: PERF.md section 5 sets it
    beside the fusions' device time by hand."""
    d, _, _ = _dims(cfg)
    return _layers(cfg)[0] * (4 * d + 7 * d) * itemsize
