"""The Gated DeltaNet mixer (arXiv:2412.06464) as a layer of
:class:`~horovod_tpu.models.transformer.Transformer`: Qwen3-Next's
linear-attention layers, three of every four.

    [q ; k ; v ; z] = x W_qkvz,  [b ; a] = x W_ba        no bias
    [q ; k ; v] = silu(conv([q ; k ; v]))                 depthwise, causal,
                                                          zero before the
                                                          sequence, no bias
    beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)
                                                          a value head, fp32
    q = q / |q| / sqrt(K),  k = k / |k|                   over a key head
    S   <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
    o_t  = S^T q_t                                        a value head
    y    = o / rms(o) * w_n * silu(z)                     over a value head,
                                                          one w_n [V] for all
    out  = y W_o

Key head ``j`` serves the value heads ``j * r .. (j + 1) * r - 1``, ``r`` the
value heads a key head.  The rule runs in its chunked form
(``kernels/gated_delta.py``: its kernels on a TPU, ``jax.numpy`` elsewhere),
and so does the convolution (``kernels/causal_conv.py``, the Mamba-2 mixer's
too, here without a bias: its kernels read ``[q ; k ; v]`` where it lies in
``W_qkvz``'s output).

**The columns' order.**  ``W_qkvz``'s columns are all the key heads' ``q``,
then all their ``k``, then the value heads' ``v``, then their ``z``, each
head by head (2048 + 2048 + 4096 + 4096 for Qwen3-Next), and ``W_ba``'s are
the value heads' ``b`` then their ``a``; the convolution's channels are ``[q
; k ; v]`` in that order, which is also the release's.  The release
interleaves the two projections by key head (``[q_j ; k_j ; v_2j, v_2j+1 ;
z_2j, z_2j+1]`` and ``[b_2j, b_2j+1 ; a_2j, a_2j+1]``): on fresh weights a
fixed permutation of columns, which :func:`release_columns` gives for a
checkpoint's.

Loaded where a layer of kind ``mixer="gated_delta"`` is built, not with
``horovod_tpu.models``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..core.timeline import scope
from ..kernels import gated_delta
from ..kernels.causal_conv import causal_conv
from .transformer import TransformerConfig, _dense


def sizes(cfg: TransformerConfig):
    """(key heads, value heads, a key head's width, a value head's, the keys'
    whole width, the values')."""
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    if hk <= 0 or hv % hk:
        raise ValueError(f"{hv} value heads on {hk} key heads")
    return (hk, hv, cfg.gdn_key_dim, cfg.gdn_value_dim, hk * cfg.gdn_key_dim,
            hv * cfg.gdn_value_dim)


def release_columns(cfg: TransformerConfig):
    """(for ``W_qkvz``, for ``W_ba``): the release's column that each of this
    module's columns is, so that ``released[:, columns]`` is this module's
    kernel."""
    hk, hv, dk, dv, _, _ = sizes(cfg)
    per = hv // hk
    group = 2 * dk + 2 * per * dv
    head = np.arange(hk)[:, None] * group
    parts = [head + np.arange(dk), head + dk + np.arange(dk),
             head + 2 * dk + np.arange(per * dv),
             head + 2 * dk + per * dv + np.arange(per * dv)]
    pair = np.arange(hk)[:, None] * 2 * per
    return (np.concatenate([p.reshape(-1) for p in parts]),
            np.concatenate([(pair + np.arange(per)).reshape(-1),
                            (pair + per + np.arange(per)).reshape(-1)]))


def _l2_normed(x, scale: float = 1.0):
    """``x / |x|`` over the last axis (``x * rsqrt(sum x^2 + 1e-6)``, the
    release's), times ``scale``; in fp32, the result in ``x``'s dtype."""
    f = x.astype(jnp.float32)
    return (f * (jax.lax.rsqrt(jnp.sum(f * f, axis=-1, keepdims=True) + 1e-6)
                 * scale)).astype(x.dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """The release's ``A_log = log U(0, 16)`` (Mamba-2's draws from 1), the
    draw kept off 0 so that the logarithm is finite."""
    return jnp.log(jax.random.uniform(key, shape, dtype,
                                      jnp.finfo(dtype).tiny, 16.0))


class GatedDeltaNet(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        hk, hv, dk, dv, key_dim, value_dim = sizes(cfg)
        b, s, _ = x.shape
        f32 = jnp.float32
        with scope("gdn.proj"):
            qkvz = _dense(cfg, 2 * key_dim + 2 * value_dim,
                          (None, cfg.model_axis), "in_proj_qkvz")(x)
            ba = _dense(cfg, 2 * hv, (None, cfg.model_axis), "in_proj_ba")(x)
        qkv, z = jnp.split(qkvz, [2 * key_dim + value_dim], axis=-1)
        taps = self.param("conv", nn.initializers.normal(0.02),
                          (2 * key_dim + value_dim, cfg.gdn_conv), f32)
        with scope("gdn.conv"):
            qkv = causal_conv(qkv, taps, within=(qkvz, 0))
        q, k, v = jnp.split(qkv, [key_dim, 2 * key_dim], axis=-1)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), f32)
        a_log = self.param("A_log", _a_log_init, (hv,), f32)
        with scope("gdn.gates"):
            beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
            g = -jnp.exp(a_log) * nn.softplus(ba[..., hv:].astype(f32)
                                              + dt_bias)
            q = _l2_normed(q.reshape(b, s, hk, dk), dk ** -0.5)
            k = _l2_normed(k.reshape(b, s, hk, dk))
        with scope("gdn.rule"):
            o = gated_delta.gated_delta(q, k, v.reshape(b, s, hv, dv), g,
                                        beta)
        scale = self.param("norm", nn.initializers.ones, (dv,), f32)
        with scope("gdn.norm"):
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
            y = (o * scale * nn.silu(z.astype(f32)).reshape(b, s, hv, dv)) \
                .astype(cfg.dtype).reshape(b, s, value_dim)
        with scope("gdn.proj"):
            return _dense(cfg, cfg.d_model, (cfg.model_axis, None),
                          "out_proj")(y)


