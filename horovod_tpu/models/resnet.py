"""ResNet v1.5 — the flagship benchmark model.

The reference benchmarks ResNet-50 via the frameworks' model zoos
(`examples/tensorflow2/tensorflow2_synthetic_benchmark.py` uses
``tf.keras.applications.ResNet50``; `examples/pytorch/
pytorch_synthetic_benchmark.py` uses torchvision).  This is the same
architecture (v1.5: stride-2 in the 3x3 of the bottleneck, like both zoos)
written TPU-first in flax: NHWC layout (TPU-native), bfloat16 compute with
fp32 BatchNorm statistics and fp32 params, shapes static so XLA tiles convs
onto the MXU.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..core.timeline import scope

ModuleDef = Any


class BatchNorm(nn.BatchNorm):
    """flax's BatchNorm, statistics and normalisation, under the scope
    ``bn``: inside a stage's scope it is the innermost, so BatchNorm's share
    of the device's time and the convolutions' separate.  The name keeps the
    parameters' paths (``BatchNorm_0``)."""

    def __call__(self, x, *args, **kwargs):
        with scope("bn"):
            return super().__call__(x, *args, **kwargs)


class BottleneckBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)
    # When set (a partial of kernels.FusedConv1x1BN), every conv(1x1)+BN
    # pair runs the pallas fused-statistics kernel — the structural lever
    # for the BN-stat HBM re-read (ROADMAP.md Q1.4).  The 3x3 stays on
    # XLA's conv.
    fused_cb: ModuleDef = None

    @nn.compact
    def __call__(self, x):
        residual = x
        if self.fused_cb is not None:
            y = self.fused_cb(self.filters)(x)
        else:
            y = self.norm()(self.conv(self.filters, (1, 1))(x))
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        if self.fused_cb is not None:
            y = self.fused_cb(self.filters * 4,
                              scale_init=nn.initializers.zeros)(y)
        else:
            y = self.norm(scale_init=nn.initializers.zeros)(
                self.conv(self.filters * 4, (1, 1))(y))
        if residual.shape != y.shape:
            if self.fused_cb is not None:
                residual = self.fused_cb(self.filters * 4,
                                         strides=self.strides,
                                         name="fused_proj")(residual)
            else:
                residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                     name="conv_proj")(residual)
                residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class BasicBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """NHWC inputs ``[batch, H, W, 3]`` → logits ``[batch, num_classes]``."""

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    # BN statistics precision/algorithm levers (benchmarks/resnet_levers.py
    # measures them; ROADMAP.md Q1.4 records the verdicts).  Defaults are
    # the numerically safe flax behavior: fp32 reductions, one-pass
    # E[x^2]-E[x]^2 variance.
    bn_f32_stats: bool = True
    bn_fast_variance: bool = True
    # Fuse BN statistics into the 1x1 convs' pallas epilogue
    # (kernels/conv_bn_stats.py) — only meaningful for BottleneckBlock.
    fuse_conv1x1_bn: bool = False
    # For multi-device training: the Mesh whose "data" axis shards the
    # batch (the fused kernel runs under shard_map with psum'd stats).
    # None = single-device kernel.
    fused_bn_mesh: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        bn_momentum, bn_epsilon = 0.9, 1e-5  # shared by BOTH norm paths
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype,
                                 param_dtype=jnp.float32)
        norm = functools.partial(BatchNorm, use_running_average=not train,
                                 momentum=bn_momentum, epsilon=bn_epsilon,
                                 dtype=self.dtype, param_dtype=jnp.float32,
                                 force_float32_reductions=self.bn_f32_stats,
                                 use_fast_variance=self.bn_fast_variance)
        fused_cb = None
        if self.fuse_conv1x1_bn:
            if not (self.bn_f32_stats and self.bn_fast_variance):
                # The fused kernel is hardwired to fp32 one-pass stats;
                # mixing it with the other BN levers would silently give
                # the 1x1 and 3x3 norms different statistics algorithms.
                raise ValueError(
                    "fuse_conv1x1_bn=True requires the default BN config "
                    "(bn_f32_stats=True, bn_fast_variance=True); the "
                    "fused kernel computes fp32 one-pass statistics only")
            from ..kernels import FusedConv1x1BN

            fused_cb = functools.partial(
                FusedConv1x1BN, dtype=self.dtype, momentum=bn_momentum,
                epsilon=bn_epsilon, use_running_average=not train,
                mesh=self.fused_bn_mesh)
        with scope("resnet.stem"):
            x = x.astype(self.dtype)
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
            x = norm(name="bn_init")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=((1, 1), (1, 1)))
        block_kwargs = {}
        if fused_cb is not None:
            if self.block_cls is not BottleneckBlock:
                # Silently building unfused would let a run labeled
                # "fused" measure the baseline.
                raise ValueError(
                    "fuse_conv1x1_bn=True is only implemented for "
                    f"BottleneckBlock (got {self.block_cls!r})")
            block_kwargs["fused_cb"] = fused_cb
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                with scope(f"resnet.stage{i + 1}"):
                    x = self.block_cls(self.num_filters * 2 ** i,
                                       conv=conv, norm=norm, strides=strides,
                                       **block_kwargs)(x)
        with scope("resnet.head"):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=jnp.float32,
                         param_dtype=jnp.float32)(x)
        return x


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2],
                             block_cls=BasicBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BottleneckBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3],
                              block_cls=BottleneckBlock)
