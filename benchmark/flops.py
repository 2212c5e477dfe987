"""Operations the algorithm needs, from shapes alone. One convention, stated
here and used by every metric that divides by a peak:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
* training is forward plus backward, three times the forward's products;
  operations recomputed to save memory (remat, the flash backward's
  recomputed scores) are NOT counted: they are the implementation's choice;
* causal attention is counted at half of the full ``T x T`` products: the
  masked half is not needed.
"""


def gpt2_matmul_params(cfg):
    """Parameters that sit in a matrix product once per token: the blocks'
    qkv, out, fc and proj kernels and the tied output head. Embedding
    look-ups, biases and norms are left out."""
    d = cfg["n_embd"]
    vocab = cfg["assumed"]["vocab_padded"]
    return cfg["n_layer"] * 12 * d * d + vocab * d


def gpt2_attention_fwd_flops_per_token(cfg, seq_len):
    """QK^T and PV of every block for one token of a ``seq_len`` row, causal
    at half: ``2 * (2 T d) / 2`` per block."""
    return cfg["n_layer"] * 2 * seq_len * cfg["n_embd"]


def gpt2_train_flops_per_token(cfg, seq_len):
    """``6 N`` for the dense products plus three times the attention
    forward."""
    return (6 * gpt2_matmul_params(cfg)
            + 3 * gpt2_attention_fwd_flops_per_token(cfg, seq_len))


def flash_train_flops_per_token(cfg, seq_len):
    """What the flash kernels of one training step have to do per token:
    the forward (2 products) and the backward (5 products: dV, dP, dS->dQ,
    dS->dK, and the scores it needs again, which the algorithm requires
    because the forward keeps no scores), causal at half. The forward run
    again under remat is not counted."""
    return 3.5 * gpt2_attention_fwd_flops_per_token(cfg, seq_len)


def flash_train_bytes_per_token(cfg, seq_len, itemsize=2):
    """Bytes the same calls must move per token if every operand were read
    and every result written once: forward q,k,v in and o out; backward
    q,k,v,o,do in and dq,dk,dv out."""
    return cfg["n_layer"] * 12 * cfg["n_embd"] * itemsize
