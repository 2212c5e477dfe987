"""ResNet v1.5 for the headline benchmark (reference config: ResNet-50
ImageNet via ``examples/pytorch`` + DistributedOptimizer).

TPU-first choices: NHWC layout (XLA's native conv layout on TPU), bf16
compute with fp32 batch-norm statistics and fp32 final logits, stride-2 on
the 3x3 conv (v1.5, like torchvision's resnet50 used by the reference
example).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BasicBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    # Mesh axis for cross-replica (sync) batch norm: when set, batch moments
    # are pmean-ed over this axis (upstream horovod/torch/sync_batch_norm.py
    # semantics) — use inside shard_map with the axis bound. None = local BN.
    bn_cross_replica_axis: str | None = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        if self.bn_cross_replica_axis is not None:
            from horovod_tpu.ops.sync_batch_norm import SyncBatchNorm
            norm = partial(SyncBatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           param_dtype=jnp.float32,
                           axis_name=self.bn_cross_replica_axis)
        else:
            norm = partial(nn.BatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           param_dtype=jnp.float32)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2),
                 padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(self.num_filters * 2 ** i, conv=conv,
                                   norm=norm, act=nn.relu, strides=strides)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32,
                     param_dtype=jnp.float32)(x)
        return x


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=BottleneckBlock)
