"""Pallas TPU kernels for ops XLA's default lowering leaves on the table.

Currently: the conv(1x1)+BatchNorm-statistics epilogue fusion
(:mod:`.conv_bn_stats`) targeting the measured ResNet-50 bottleneck —
BN statistics re-reading every activation from HBM (they lead the device's
op list in ``resnet50-wfbp-1chip``: ``PERF.md`` §5, ``ROADMAP.md`` Q1.4)."""

from .conv_bn_stats import (  # noqa: F401
    FusedConv1x1BN,
    matmul_bn_stats,
    sharded_matmul_bn_stats,
)
