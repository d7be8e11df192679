"""The Mamba-2 mixer (arXiv:2405.21060) as a layer of
:class:`~horovod_tpu.models.transformer.Transformer`: Nemotron-H's ``M``
layers.

    [z, xBC, dt] = split(u W_in)                      no bias
    xBC   = silu(conv(xBC) + b)                       depthwise, causal, zero
                                                      before the sequence
    [x, B, C] = split(xBC)
    delta = softplus(dt + dt_bias),  a = -exp(A_log)  a head, in fp32
    S_t   = exp(delta_t a) S_{t-1} + delta_t x_t B_t^T
    y_t   = S_t C_t + D x_t                           B, C of the head's group
    y     = RMSNorm_g(y * silu(z))                    over each group's
                                                      channels
    out   = y W_out

The recurrence runs in its chunked form (``kernels/ssd_scan.py``: its kernels
on a TPU, ``jax.numpy`` elsewhere), and so does the convolution
(``kernels/causal_conv.py``, the Gated DeltaNet's too: its kernels read
``xBC`` where it lies in ``W_in``'s output and write it once a pass).

**A share of the heads.**  The norm is taken group by group, a head reads the
``B`` and ``C`` of its own group and the convolution is depthwise, so the
groups of a mixer never meet before ``W_out``: a chip that holds
``cfg.mamba_groups_held`` of the ``cfg.mamba_groups`` groups holds those
columns of ``W_in``, those channels of the convolution and the norm and those
rows of ``W_out`` (:func:`share_of` cuts them out of the whole mixer's
parameters), and what it returns is its groups' part of ``out``; the parts of
all the groups add up to the whole mixer's (``tests/test_nemotron.py``).  On
one chip that part goes on to the next layer as it is; nothing stands in for
the sum over the chips.

**One group is no share.**  granite-4.0-h-micro's mixer has 64 heads of 64
in **one** group (``mamba_groups=1``): the gated norm then runs over all
4096 channels and every head reads the same ``B`` and ``C``, so the norm's
mean square couples every head with every other and a subset of the heads
is no part of any sum.  ``mamba_groups_held`` can only be ``None`` or
``(0,)`` there, both the whole mixer (``tests/test_granite.py``); sharing
such a mixer among chips would take a reduction across them inside the
norm, which this module does not have (``ROADMAP.md``, Queue 2).  That
configuration holds its mixers whole, and its scan runs a grid step over the
whole group: 64 heads, 32 lane pairs, 2 MB of states in VMEM
(``kernels/ssd_scan.py``).  Granite applies its ``residual_multiplier`` to
what this module returns, in ``Block``; nothing of the four muP scalars is
in here.

Loaded where a layer of kind ``mixer="mamba2"`` is built, not with
``horovod_tpu.models``.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..core.timeline import scope
from ..kernels import ssd_scan
from ..kernels.causal_conv import causal_conv
from .transformer import TransformerConfig, _dense


def sizes(cfg: TransformerConfig):
    """(groups held here, heads held, a head's channels, the state's width,
    the inner width held, the convolution's channels held)."""
    if cfg.mamba_heads % cfg.mamba_groups:
        raise ValueError(f"{cfg.mamba_heads} heads in {cfg.mamba_groups} "
                         f"groups")
    groups = cfg.mamba_groups if cfg.mamba_groups_held is None \
        else len(cfg.mamba_groups_held)
    heads = cfg.mamba_heads // cfg.mamba_groups * groups
    inner = heads * cfg.mamba_head_dim
    return (groups, heads, cfg.mamba_head_dim, cfg.mamba_state, inner,
            inner + 2 * groups * cfg.mamba_state)


def _dt_bias_init(cfg: TransformerConfig):
    """The Mamba-2 code's: ``dt`` log-uniform between the limits, clamped at
    the floor, and the bias its inverse softplus."""
    low, high, floor = cfg.mamba_dt_limits

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (math.log(high) - math.log(low)) + math.log(low))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _conv_init(taps: int):
    bound = taps ** -0.5        # torch's Conv1d default: 1 / sqrt(fan in)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class Mamba2(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        groups, heads, p, n, inner, conv_dim = sizes(cfg)
        b, s, _ = u.shape
        f32 = jnp.float32
        with scope("ssm.proj"):
            zxbcdt = _dense(cfg, inner + conv_dim + heads,
                            (None, cfg.model_axis), "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
        taps = self.param("conv", _conv_init(cfg.mamba_conv),
                          (conv_dim, cfg.mamba_conv), f32)
        conv_bias = self.param("conv_bias", _conv_init(cfg.mamba_conv),
                               (conv_dim,), f32)
        with scope("ssm.conv"):
            xbc = causal_conv(xbc, taps, conv_bias, within=(zxbcdt, inner))
        x, bm, cm = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (heads,), f32)
        a_log = self.param("A_log", _a_log_init, (heads,), f32)
        skip = self.param("D", nn.initializers.ones, (heads,), f32)
        with scope("ssm.scan"):
            x = x.reshape(b, s, heads, p)
            y = ssd_scan.ssd_scan(
                x, nn.softplus(dt.astype(f32) + dt_bias), -jnp.exp(a_log),
                bm.reshape(b, s, groups, n), cm.reshape(b, s, groups, n),
                chunk=cfg.mamba_chunk)
            y = y.astype(f32) + skip[:, None] * x.astype(f32)
        scale = self.param("norm", nn.initializers.ones, (inner,), f32)
        with scope("ssm.norm"):
            y = y.reshape(b, s, groups, inner // groups) \
                * nn.silu(z.astype(f32)).reshape(b, s, groups, -1)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
            y = (y.reshape(b, s, inner) * scale).astype(cfg.dtype)
        with scope("ssm.proj"):
            return _dense(cfg, cfg.d_model, (cfg.model_axis, None),
                          "out_proj")(y)


def share_of(params, cfg: TransformerConfig, held):
    """The parameters of the share that holds the groups ``held`` out of a
    whole mixer's ``params`` (``cfg`` the whole mixer's, ``mamba_groups_held``
    None)."""
    groups, heads, p, n, inner, _ = sizes(cfg)
    per = heads // groups
    held = jnp.asarray(held)

    def of(width):
        """The held groups' indices among ``groups * width`` channels."""
        return (held[:, None] * width + jnp.arange(width)).reshape(-1)

    channels, state, head = of(per * p), of(n), of(per)
    # [z, x, B, C, dt] along W_in's columns; [x, B, C] along the conv's.
    conv = jnp.concatenate([channels, inner + state,
                            inner + groups * n + state])
    columns = jnp.concatenate([channels, inner + conv,
                               2 * inner + 2 * groups * n + head])
    return {
        "in_proj": {"kernel": params["in_proj"]["kernel"][:, columns]},
        "conv": params["conv"][conv], "conv_bias": params["conv_bias"][conv],
        "dt_bias": params["dt_bias"][head], "A_log": params["A_log"][head],
        "D": params["D"][head], "norm": params["norm"][channels],
        "out_proj": {"kernel": params["out_proj"]["kernel"][channels]}}
