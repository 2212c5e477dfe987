"""Batch norm with a tunable statistics dtype + the space-to-depth stem.

The round-4 roofline analysis pinned ResNet-50 at ~32% HFU with the BN
statistics passes as the bound: flax's ``nn.BatchNorm`` always promotes
moment accumulation to float32 (`flax/linen/normalization._compute_stats`),
so every BN reads its activation tensor at fp32 bandwidth. The two
experiments that analysis prescribed sit behind flags (VERDICT r3 item 6);
both measured negative on the chip (ROADMAP "Closed — do not retry"):

- :class:`TunableBatchNorm` — flax-BatchNorm-compatible module (same
  params/batch_stats layout, checkpoint-interchangeable) whose moment
  accumulation dtype is a field: ``stats_dtype=jnp.bfloat16`` halves the
  HBM traffic of the statistics passes at the cost of bf16 moment
  rounding (running stats stay fp32). Supports ``axis_name`` for the
  cross-replica (sync) variant like upstream
  ``horovod/torch/sync_batch_norm.py``.
- :func:`space_to_depth` — the MLPerf stem transform: the 7x7/s2 conv on
  C=3 pads 3 channels up to the native 8/128 tile on TPU; re-laying the
  input as (H/2, W/2, 12) and running a 4x4/s1 conv is the same math
  (see :func:`horovod_tpu.models.resnet.convert_stem_weights`) with 4x
  the channel utilisation.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

__all__ = ["TunableBatchNorm", "space_to_depth"]


def space_to_depth(x: jnp.ndarray, block: int = 2) -> jnp.ndarray:
    """NHWC space-to-depth: (N, H, W, C) -> (N, H/b, W/b, b*b*C).

    Output channel index is ``(a, b, c)`` row-major — spatial row offset
    ``a``, column offset ``b``, then the original channel — the layout
    :func:`~horovod_tpu.models.resnet.convert_stem_weights` assumes.
    """
    n, h, w, c = x.shape
    if h % block or w % block:
        raise ValueError(f"spatial dims {(h, w)} not divisible by "
                         f"block {block}")
    x = x.reshape(n, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // block, w // block, block * block * c)


class TunableBatchNorm(nn.Module):
    """``flax.linen.BatchNorm`` semantics with a configurable moment
    accumulation dtype.

    Variable layout matches flax BatchNorm exactly (``batch_stats``:
    ``mean``/``var`` fp32; ``params``: ``scale``/``bias``), so a model can
    flip between the two checkpoint-compatibly. With
    ``stats_dtype=jnp.float32`` the numerics match flax (fast-variance
    E[x^2]-E[x]^2 form); ``jnp.bfloat16`` is the bandwidth experiment.
    """

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = None                 # output dtype (None = input dtype)
    param_dtype: Any = jnp.float32
    stats_dtype: Any = jnp.float32    # moment accumulation dtype (the knob)
    axis_name: Optional[str] = None   # pmean moments over this mesh axis
    use_scale: bool = True
    use_bias: bool = True
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros

    @nn.compact
    def __call__(self, x):
        feat = x.shape[-1]
        ra_mean = self.variable("batch_stats", "mean",
                                lambda *_: jnp.zeros(feat, jnp.float32),
                                feat)
        ra_var = self.variable("batch_stats", "var",
                               lambda *_: jnp.ones(feat, jnp.float32),
                               feat)

        if self.use_running_average:
            mean = ra_mean.value
            var = ra_var.value
        else:
            axes = tuple(range(x.ndim - 1))
            xs = x.astype(self.stats_dtype)
            mean = jnp.mean(xs, axes)
            mean2 = jnp.mean(lax.square(xs), axes)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean2 = lax.pmean(mean2, self.axis_name)
            # fast-variance form (flax's default): one fused pass over x.
            var = jnp.maximum(mean2 - lax.square(mean), 0.0)
            mean = mean.astype(jnp.float32)
            var = var.astype(jnp.float32)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var

        y = x.astype(self.stats_dtype)
        y = (y - mean.astype(y.dtype)) * lax.rsqrt(
            var.astype(y.dtype) + jnp.asarray(self.epsilon, y.dtype))
        if self.use_scale:
            scale = self.param("scale", self.scale_init, (feat,),
                               self.param_dtype)
            y = y * scale.astype(y.dtype)
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (feat,),
                              self.param_dtype)
            y = y + bias.astype(y.dtype)
        out_dtype = self.dtype if self.dtype is not None else x.dtype
        return y.astype(out_dtype)
