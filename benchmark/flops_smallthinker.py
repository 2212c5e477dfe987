"""Operations and bytes the SmallThinker configuration needs, from shapes
alone, in ``flops.py``'s convention: a product of ``m x k`` by ``k x n`` is
``2 m k n``; training is three times the forward's products; recomputed
operations (remat, the flash backward's scores) are not counted. What a
position meets in every layer:

* four attention projections with the key/value heads as published (4 serve
  28) and the attention products over the pairs the layer's mask shows,
  **whatever kernel runs them**: a global layer (``sliding_window_layout[i]
  = 0``) shows the causal triangle, counted at half of the ``T x T``
  products; a window layer shows the band, ``W T - W^2 / 2`` pairs a row of
  ``T > W`` positions (``T - W`` rows of ``W`` keys under a triangle of
  ``W``), and the triangle where the row is no longer than the window;
* the router over its full width, and the ReGLU experts at what this chip's
  share requires: each position's ``moe_num_active_primary_experts`` choices
  fall on a held expert with probability ``held / router_width``.

The embedding is a gather; the untied head over the vocabulary slice is a
product, once.
"""


def _dims(cfg):
    """``(d, query width, key/value width)``."""
    head = cfg["head_dim"]
    return (cfg["hidden_size"], cfg["num_attention_heads"] * head,
            cfg["num_key_value_heads"] * head)


def _layers(cfg):
    """``(global, window)`` attention layers of the configuration."""
    windowed = sum(1 for w in cfg["sliding_window_layout"] if w)
    return cfg["num_hidden_layers"] - windowed, windowed


def matmul_params(cfg):
    """Parameters one position meets in a matrix product in the whole
    model, the head over the vocabulary slice included."""
    d, heads, kv = _dims(cfg)
    width = cfg["deployment"]["router_width"]
    share = cfg["moe_num_primary_experts"] / width
    return (cfg["num_hidden_layers"] * (
                2 * d * heads + 2 * d * kv + d * width
                + cfg["moe_num_active_primary_experts"] * share
                * 3 * d * cfg["moe_ffn_hidden_size"])
            + cfg["vocab_size"] * d)


def pairs_per_token(cfg, seq_len):
    """``(global, window)``: visible (query, key) pairs a token of a
    ``seq_len`` row, on average over the row, in a global and in a window
    layer."""
    window = min(cfg["sliding_window_size"], seq_len)
    return seq_len / 2, window - window * window / (2 * seq_len)


def attention_fwd_flops_per_token(cfg, seq_len):
    """QK^T and PV of every attention layer for one token of a ``seq_len``
    row: 2 products of ``2 x heads x head_dim`` a visible pair."""
    _, heads, _ = _dims(cfg)
    full, band = pairs_per_token(cfg, seq_len)
    layers = _layers(cfg)
    return 4 * heads * (layers[0] * full + layers[1] * band)


def train_flops_per_token(cfg, seq_len):
    """``6 x`` the parameters met plus three times the attention forward."""
    return (6 * matmul_params(cfg)
            + 3 * attention_fwd_flops_per_token(cfg, seq_len))


def flash_train_flops_per_token(cfg, seq_len):
    """What the flash kernels of one training step have to do per token:
    the forward (2 products) and the backward (5: dV, dP, dS->dQ, dS->dK and
    the scores again, which the algorithm requires because the forward keeps
    none), over the band and the triangle. The forward run again under remat
    is not counted."""
    return 3.5 * attention_fwd_flops_per_token(cfg, seq_len)


def flash_train_bytes_per_token(cfg, seq_len, itemsize=2):
    """Bytes the same calls must move per token if every operand were read
    and every result written once, with the key/value heads as published
    (grouped-query: 4 heads serve 28): forward q, k, v in and o out;
    backward q, k, v, o, do in and dq, dk, dv out."""
    _, heads, kv = _dims(cfg)
    fwd = 2 * heads + 2 * kv
    bwd = (3 * heads + 2 * kv) + (heads + 2 * kv)
    return cfg["num_hidden_layers"] * (fwd + bwd) * itemsize
