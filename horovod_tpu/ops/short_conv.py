"""The gated short convolution of the LFM2 family's ``conv`` layers.

Between two matrix products (``in_proj``: d -> 3d, ``out_proj``: d -> d, the
model's own ``nn.Dense``) such a layer does element-wise work with a halo of
``K - 1`` positions along the sequence: the projection's three groups of
``d`` channels are an input gate ``B``, an output gate ``C`` and the value
``X``; ``z = B * X`` goes through a depthwise causal convolution of ``K``
taps, and ``C`` gates what comes out. Bandwidth-bound: about ``3 d`` values
read and ``d`` written a position, a handful of multiply-adds each. Plain
XLA (shifted multiply-adds, fp32 accumulation), which fuses it into one pass;
there is no kernel here.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["gated_short_conv"]


def gated_short_conv(bcx: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """``C * conv(B * X)`` for ``bcx`` (batch, T, 3 d), whose last axis holds
    ``B``, ``C`` and ``X`` in that order, and ``taps`` (d, K):

        ``out[t] = C[t] * sum_{j < K} taps[:, j] * (B * X)[t - (K - 1) + j]``

    with positions before the row's first counted as zero, so ``out[t]``
    depends on inputs ``t - K + 1 .. t`` alone (tap ``K - 1`` weighs the
    position itself, as a ``Conv1d`` left-padded by ``K - 1`` has it). The
    products and the sum run in fp32; the result (batch, T, d) has
    ``bcx``'s dtype.
    """
    if bcx.ndim != 3 or bcx.shape[-1] % 3:
        raise ValueError(f"bcx must be (batch, T, 3 d), got {bcx.shape}")
    d, (T, K) = bcx.shape[-1] // 3, (bcx.shape[1], taps.shape[-1])
    if taps.shape != (d, K):
        raise ValueError(f"taps must be (d, K) = ({d}, K), got {taps.shape}")
    b, c, x = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32)
               for i in range(3))
    z = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    w = taps.astype(jnp.float32)
    acc = w[:, 0] * z[:, :T]
    for j in range(1, K):
        acc = acc + w[:, j] * z[:, j:j + T]
    return (c * acc).astype(bcx.dtype)
