"""Fused 1x1-conv + BatchNorm-statistics pallas kernel (TPU).

The measured ResNet-50 plateau (``PERF.md`` §5, ``ROADMAP.md`` Q1.4): XLA
emits the conv, writes the activation to HBM, then a separate
reduce-fusion re-reads the WHOLE activation to compute BatchNorm's
per-channel sum / sum-of-squares — the fusions that lead the device's op
list, and the one structural lever left once the cheap ones
(``benchmarks/resnet_levers.py``) were measured and rejected.  Convs are fusion roots in XLA; the compiler will not sink
a cross-batch reduction into the conv epilogue, so this kernel does it by
hand for the convs where that is tractable: 1x1 convolutions, which are
plain matmuls over ``[N*H*W, Cin] @ [Cin, Cout]`` and carry roughly half
of ResNet-50's conv count (two of three convs in every bottleneck block,
plus every projection shortcut).

Kernel shape: a blocked MXU matmul (grid ``i, j, k``; fp32 VMEM
accumulator over the ``k`` blocks) whose epilogue — while the output tile
is still in VMEM — reduces the tile's per-channel sum and sum-of-squares
and writes them to per-``i`` partial rows; a tiny XLA reduction collapses
the partials.  The activation is therefore read ZERO extra times for
statistics (baseline: one full extra HBM read).

Reference role: the fused-BN path of the reference's model zoos is cuDNN
``conv+BN`` fusion on GPU (e.g. ``tf.keras`` ResNet under XLA:GPU/cuDNN);
there is no reference source file to cite — the reference gets this from
its vendor library, we get it from pallas.

Numerics: accumulation and statistics in fp32 (like the shipped
``force_float32_reductions`` BN config); output cast to the model dtype
(bf16).  Verified against the unfused composition for values and
gradients: interpreted on CPU (``tests/test_conv_bn_kernel.py``) and
compiled on the chip at ResNet-50's 1x1 shapes (``chip_smoke.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

_DEF_BM = 256
_DEF_BN = 256
_DEF_BK = 256


# Height of an fp32 VMEM tile: the statistics partials leave the kernel as
# whole (8, bn) tiles, because Mosaic rejects an output block whose
# second-to-last dimension is neither a multiple of 8 nor the full array.
_SUBLANES = 8


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def _matmul_stats_kernel(x_ref, w_ref, y_ref, s1_ref, s2_ref, acc_ref):
    """One (i, j, k) grid step: accumulate the MXU partial product; on the
    last k block, emit the output tile and its per-channel stats partials.

    Zero-padding correctness: padded M rows produce y == 0 rows which
    contribute exactly 0 to both sum and sum-of-squares, so stats need no
    masking; padded K columns multiply zeros into the product."""
    import jax.experimental.pallas as pl

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(x_ref[:], w_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[:]
        y_ref[:] = acc.astype(y_ref.dtype)
        # Per-channel partials for THIS i block, one row per sublane (row
        # r sums tile rows r, r+8, ...: elementwise adds of whole vregs,
        # no cross-sublane reduce); collapsed outside.
        bm, bn = acc.shape
        part = acc.reshape(bm // _SUBLANES, _SUBLANES, bn)
        s1_ref[:] = jnp.sum(part, axis=0)
        s2_ref[:] = jnp.sum(part * part, axis=0)


def _matmul_stats_fwd_pallas(x: jnp.ndarray, w: jnp.ndarray,
                             bm: int, bn: int, bk: int
                             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    if bm % _SUBLANES:
        raise ValueError(f"bm={bm} must be a multiple of {_SUBLANES}")
    # A K that fits one block is taken whole (a block may span a full
    # array dimension of any size), so K=64 is not padded to 256.
    bk = min(bk, k)
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w, 0, bk), 1, bn)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    gi, gj, gk = mp // bm, np_ // bn, kp // bk

    y, s1p, s2p = pl.pallas_call(
        _matmul_stats_kernel,
        grid=(gi, gj, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((_SUBLANES, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((_SUBLANES, bn), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), x.dtype),
            jax.ShapeDtypeStruct((gi * _SUBLANES, np_), jnp.float32),
            jax.ShapeDtypeStruct((gi * _SUBLANES, np_), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # Compiled on a TPU, interpreted everywhere else; never a choice.
        interpret=jax.default_backend() != "tpu",
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * np_ * kp,
            bytes_accessed=(mp * kp + kp * np_) * x.dtype.itemsize
            + mp * np_ * x.dtype.itemsize,
            transcendentals=0,
        ),
    )(xp, wp)
    return (y[:m, :n], jnp.sum(s1p, axis=0)[:n], jnp.sum(s2p, axis=0)[:n])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def matmul_bn_stats(x: jnp.ndarray, w: jnp.ndarray,
                    bm: int = _DEF_BM, bn: int = _DEF_BN, bk: int = _DEF_BK
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``y = x @ w`` plus per-channel ``(sum(y), sum(y*y))`` in one pass.

    ``x``: ``[M, K]`` (model dtype, e.g. bf16), ``w``: ``[K, N]``.
    Returns ``(y [M,N] in x.dtype, s1 [N] f32, s2 [N] f32)``.
    Off-TPU (CPU tests / virtual meshes) the pallas interpreter runs the
    same kernel body."""
    return _matmul_stats_fwd_pallas(x, w, bm, bn, bk)


def _fwd_rule(x, w, bm, bn, bk):
    y, s1, s2 = _matmul_stats_fwd_pallas(x, w, bm, bn, bk)
    return (y, s1, s2), (x, w, y)


def _bwd_rule(bm, bn, bk, residuals, cotangents):
    """VJP: with ``r = dy + ds1·1ᵀ + 2·y∘ds2·1ᵀ`` (the stats cotangents
    broadcast over rows), ``dx = r @ wᵀ`` and ``dw = xᵀ @ r`` — plain XLA
    matmuls; the fusion win targeted the forward stats read.

    Precision note: the ``2·y∘ds2`` term uses the SAVED output ``y``
    (model dtype, e.g. bf16) — the same rounded activation the unfused
    baseline's backward reads from HBM for its dvar terms.  Exact in
    f32 (``y == acc``); for bf16 the rounding matches the baseline's,
    while the forward statistics (from the f32 accumulator) are strictly
    more precise than the baseline's bf16-activation reductions."""
    x, w, y = residuals
    dy, ds1, ds2 = cotangents
    f32 = jnp.float32
    r = (dy.astype(f32) + ds1[None, :].astype(f32)
         + 2.0 * y.astype(f32) * ds2[None, :].astype(f32))
    dx = jnp.dot(r, w.astype(f32).T).astype(x.dtype)
    dw = jnp.dot(x.astype(f32).T, r).astype(w.dtype)
    return dx, dw


matmul_bn_stats.defvjp(_fwd_rule, _bwd_rule)


def sharded_matmul_bn_stats(x: jnp.ndarray, w: jnp.ndarray, mesh,
                            data_axis: str = "data"):
    """Multi-device flavor: the kernel runs per-shard under ``shard_map``
    (rows sharded on ``data_axis``, weights replicated) and the statistics
    partials are ``psum``-reduced across the axis — matching BatchNorm's
    global-batch semantics under the GSPMD train step.  This is the
    multi-chip integration the plain ``pl.pallas_call`` cannot get from
    GSPMD (it is not partitionable; unwrapped it would all-gather the
    activation)."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import shard_map_fn

    def local_fn(xs, ws):
        y, s1, s2 = matmul_bn_stats(xs, ws)
        return (y, jax.lax.psum(s1, data_axis),
                jax.lax.psum(s2, data_axis))

    return shard_map_fn(
        local_fn, mesh,
        in_specs=(P(data_axis, None), P(None, None)),
        out_specs=(P(data_axis, None), P(None), P(None)))(x, w)


# ---------------------------------------------------------------------------
# flax module: drop-in replacement for conv(1x1, no bias) + BatchNorm

import flax.linen as nn  # noqa: E402 — hard dep (resnet.py already requires it)


class FusedConv1x1BN(nn.Module):
    """``nn.Conv(features, (1,1), strides, use_bias=False)`` followed by
    ``nn.BatchNorm`` with the statistics pass fused into the conv's
    pallas epilogue (training mode).  Eval mode uses running stats and
    a plain XLA matmul — no statistics are needed there.

    Matches the model's BN config: fp32 stats, one-pass variance,
    momentum/epsilon as given, bf16 compute.  A stride-2 1x1 conv
    subsamples first (exact: a 1x1 kernel only reads the strided
    positions).

    Multi-device: pass ``mesh`` (and ``data_axis``) — the kernel then
    runs per-shard under ``shard_map`` with ``psum``-reduced statistics
    (:func:`sharded_matmul_bn_stats`), preserving BN's global-batch
    semantics.  This wrap is required because ``pl.pallas_call`` is not
    GSPMD-partitionable: unwrapped under a sharded jit it would force
    all-gathers of the activation.  Without ``mesh`` the plain
    single-device kernel runs.
    """

    features: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Any = jnp.bfloat16
    momentum: float = 0.9
    epsilon: float = 1e-5
    scale_init: Any = nn.initializers.ones
    use_running_average: bool = False
    # Multi-device: when a Mesh with >1 device on `data_axis` is given,
    # the kernel runs under shard_map with psum'd statistics (see
    # sharded_matmul_bn_stats); otherwise the plain single-device call.
    mesh: Any = None
    data_axis: str = "data"

    @nn.compact
    def __call__(self, x):
        cin = x.shape[-1]
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (cin, self.features), jnp.float32)
        scale = self.param("scale", self.scale_init,
                           (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((self.features,),
                                                  jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((self.features,),
                                                jnp.float32))

        if self.strides != (1, 1):
            sh, sw = self.strides
            x = x[:, ::sh, ::sw, :]
        batch, h, w_, _ = x.shape
        xm = x.astype(self.dtype).reshape(-1, cin)
        count = xm.shape[0]

        if self.use_running_average:
            y = jnp.dot(xm, kernel.astype(self.dtype),
                        preferred_element_type=jnp.float32)
            mean, var = ra_mean.value, ra_var.value
        else:
            wk = kernel.astype(self.dtype)
            if self.mesh is not None and \
                    dict(self.mesh.shape).get(self.data_axis, 1) > 1:
                y, s1, s2 = sharded_matmul_bn_stats(
                    xm, wk, self.mesh, self.data_axis)
            else:
                y, s1, s2 = matmul_bn_stats(xm, wk)
            y = y.astype(jnp.float32)
            mean = s1 / count
            # one-pass E[y^2] - E[y]^2 (the shipped fast-variance
            # config; measured faster than two-pass by
            # benchmarks/resnet_levers.py)
            var = jnp.maximum(s2 / count - mean * mean, 0.0)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                # biased batch variance, exactly like flax BatchNorm
                # (no Bessel correction — torch differs here)
                ra_var.value = m * ra_var.value + (1 - m) * var
        inv = jax.lax.rsqrt(var + self.epsilon) * scale
        out = (y - mean[None, :]) * inv[None, :] + bias[None, :]
        return out.astype(self.dtype).reshape(
            batch, h, w_, self.features)

