"""The Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692) as a layer
of :class:`~horovod_tpu.models.transformer.Transformer`: Ling-3.0-flash's
linear-attention layers, five of every six.

    [q ; k ; v ; f ; z] = x W_in,  b = x W_beta            no bias
    [q ; k ; v] = silu(conv([q ; k ; v]))                  depthwise, causal,
                                                           zero before the
                                                           sequence, no bias
    beta = sigmoid(b)                                      a head, fp32
    g = lower * sigmoid(exp(A_log) (f + dt_bias))          a key channel, in
                                                           (lower, 0), fp32
    q = q / |q| / sqrt(K),  k = k / |k|                    over a head
    S   <- diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
    o_t  = S^T q_t                                         a head
    y    = o / rms(o) * w_n * sigmoid(z)                   over a head, one
                                                           w_n [V] for all
    out  = y W_o

Every head has keys of its own (nothing is grouped), the decay is a vector a
key channel from one full-rank matrix (the release's ``no_kda_lora``) behind
the bounded gate (``kda_safe_gate``: ``lower`` is the release's
``kda_lower_bound``, -5, the kernels' own ``kda.LOWER_BOUND``: it is what
lets ``kernels/kda.py`` split a chunk's decays between two operands),
``A_log`` one a head and ``dt_bias`` one a channel.  No positions.
The rule runs in its chunked form (``kernels/kda.py``: its kernels on a TPU,
``jax.numpy`` elsewhere), and so does the convolution
(``kernels/causal_conv.py``, whose kernels read ``[q ; k ; v]`` where it lies
in ``W_in``'s output).  On a TPU in bf16 what stands each side of the rule,
the decay gate with the two L2 norms and the gated norm a head, runs as
``kernels/head_rows.py``'s row kernels on the flat ``[b, s, heads * 128]``
layout, which the rule's kernels take and give: no array with the heads as an
axis stands between the convolution and ``out_proj``.

**The columns' order.**  ``W_in``'s columns are all the heads' ``q``, then
their ``k``, ``v``, ``f`` and ``z``, each head by head; the release keeps
five matrices and three convolutions, which are these columns and channels
side by side: on fresh weights the same draw, for a checkpoint a
concatenation.

Loaded where a layer of kind ``mixer="kda"`` is built, not with
``horovod_tpu.models``.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..core.timeline import scope
from ..kernels import head_rows, kda
from ..kernels.causal_conv import causal_conv
from .gated_delta import _l2_normed
from .transformer import TransformerConfig, _dense


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A_log = log U(1, 16)``, flash-linear-attention's for this mixer."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniformly from 1e-3 to 1e-1
    (Mamba-2's and flash-linear-attention's): -6.9 to -2.3."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class KimiDeltaAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, dk, dv = cfg.num_heads, cfg.kda_head_dim, cfg.kda_head_dim
        inner = h * dk
        b, s, _ = x.shape
        f32 = jnp.float32
        with scope("kda.proj"):
            proj = _dense(cfg, 5 * inner, (None, cfg.model_axis),
                          "in_proj")(x)
            beta = _dense(cfg, h, (None, cfg.model_axis), "beta_proj")(x)
        qkv, f, z = jnp.split(proj, [3 * inner, 4 * inner], axis=-1)
        taps = self.param("conv", nn.initializers.normal(0.02),
                          (3 * inner, cfg.conv_taps), f32)
        with scope("kda.conv"):
            qkv = causal_conv(qkv, taps, within=(proj, 0))
        a_log = self.param("A_log", _a_log_init, (h,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,), f32)
        scale = self.param("norm", nn.initializers.ones, (dv,), f32)
        if head_rows.takes(s, dk, qkv.dtype):
            # A head is a lane group of the flat row from the convolution to
            # ``out_proj``: the kernels read q, k, f and z where they lie.
            with scope("kda.gate"):
                beta = jax.nn.sigmoid(beta.astype(f32))
                q, k, g = head_rows.gate(
                    qkv, proj, jnp.repeat(jnp.exp(a_log), dk)[None],
                    dt_bias[None], f_at=3 * inner, scale=dk ** -0.5,
                    lower=kda.LOWER_BOUND)
            with scope("kda.rule"):
                o = kda.kda_flat(q, k, qkv[..., 2 * inner:], g, beta)
            with scope("kda.norm"):
                y = head_rows.norm(o, proj, jnp.tile(scale, h)[None],
                                   z_at=4 * inner, eps=cfg.norm_eps)
        else:
            q, k, v = (t.reshape(b, s, h, dk)
                       for t in jnp.split(qkv, 3, axis=-1))
            with scope("kda.gate"):
                beta = jax.nn.sigmoid(beta.astype(f32))
                g = kda.LOWER_BOUND * jax.nn.sigmoid(
                    jnp.exp(a_log)[:, None]
                    * (f.astype(f32) + dt_bias).reshape(b, s, h, dk))
                q = _l2_normed(q, dk ** -0.5)
                k = _l2_normed(k)
            with scope("kda.rule"):
                o = kda.kda(q, k, v, g, beta)
            with scope("kda.norm"):
                o = o.astype(f32)
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
                y = (o * scale * jax.nn.sigmoid(z.astype(f32))
                     .reshape(b, s, h, dv)).astype(cfg.dtype) \
                    .reshape(b, s, inner)
        with scope("kda.out"):
            return _dense(cfg, cfg.d_model, (cfg.model_axis, None),
                          "out_proj")(y)
