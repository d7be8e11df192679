"""Manifold-constrained hyper-connections (mHC, Xie et al., arXiv:2512.24880,
over hyper-connections, Zhu et al., arXiv:2409.19606), as Xing4.0-29B-A4B
has them (``hc_mult`` 4, ``hc_sinkhorn_iters`` 20, ``hc_eps`` 1e-6,
``mhc_h_res_clamp_min|max`` -+30): the residual stream is ``n`` streams of
the model's width, every sublayer (a block's mixer, a block's FFN) reads one
mix of them and writes back to all, and the ``n x n`` matrix that carries the
streams past the sublayer is made doubly stochastic, token by token, by
Sinkhorn-Knopp normalisations.

One :class:`HyperConnection` a sublayer, with its own ``phi [n C, n + n +
n^2]``, ``bias [n + n + n^2]`` and ``alpha [3]``; ``X [n, C]`` a token's
streams:

    x~      = vec(X) / sqrt(mean(vec(X)^2) + eps)          over all n C, fp32
    H_pre   = sigmoid(a_pre  (x~ phi_pre)  + b_pre)        [n]
    H_post  = 2 sigmoid(a_post (x~ phi_post) + b_post)     [n]
    H_res   = SK(clip(a_res mat(x~ phi_res) + b_res, -c, c))   [n, n]
    SK(A)   : M = exp(A); ``iters`` times: every column over its sum + hc_eps,
              then every row over its sum + hc_eps
    u       = H_pre X                       the sublayer's input, [C]
    X'      = H_res X + H_post^T y          y the sublayer's output

**The layout is the work** (``PERF.md`` section 6, PR 58).  The coefficients
are 24 numbers a token: as ``[s, 4, 4]`` in fp32 a TPU pads each array to
``(8, 128)`` tiles, 64 times its data, and the Sinkhorn iterations saved for
the backward pass would be forty such arrays a sublayer.  Here the tokens are
the **minor** dimension of every coefficient array (``[24, b, s]``, ``[n, n,
b, s]``): the product with ``phi`` is written transposed so that it leaves
the MXU that way, the iterations are sums and products over the two leading
axes, unrolled (XLA makes a fusion of each sum and of each product, some
eighty small ones a call, whose arrays live in fast memory: the chain's
length is what they cost, not bytes), and :func:`sinkhorn` is a
``custom_vjp`` that keeps the clipped logits alone and runs the iterations
again in the backward pass, so no per-iteration array is kept.  The mixes
are sums over the ``n`` streams written out (a contraction over 4 is no
matrix product for an MXU), with fp32 accumulation.

Precision: the streams and the sublayers' matmuls in ``cfg.dtype``; the
flattened norm, the product with ``phi``, the sigmoids, the iterations and
the mixes' sums in fp32.  Over bf16 streams the product with ``phi`` is one
bf16 pass of the MXU against the three bf16 pieces of the fp32 weights side
by side (``parallel/moe.py::_bf16_pieces``: the streams are exact in bf16, so
every term is there), and its backward one pass each way.

Backward (:func:`connect`): over bf16 streams on a TPU the stream-sized work
of the backward pass is two kernels (``kernels/hyper_connection.py``), each
the backward of a ``custom_vjp`` whose forward is the text above:
:func:`_mix_back` (``dy``, ``dX~ = H_res^T dX'`` and the twenty sums ``dH_res``,
``dH_post`` in one read of ``dX'``, ``X`` and ``y``) and :func:`_pre_side`
(the norm, the product, ``H_pre`` and the mix down: ``dX`` written once).
Everything else (float32, the CPU, other shapes) is :func:`reference`, the
same forward under plain autodiff.

Scopes: ``hc.coeff`` (the flattened norm, the product with ``phi``, the
sigmoids), ``hc.sinkhorn`` (the exponential and the iterations, forward and
again backward), ``hc.pre`` (the mix down), ``hc.post`` (the mix back; the
fan-out and the fold of :class:`~horovod_tpu.models.transformer.Transformer`).
A sublayer sows ``max |row or column sum of H_res - 1|`` over its tokens into
the ``hc`` collection (``apply(..., mutable=["hc"])``, then
:func:`max_deviation`): what the iterations leave.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.timeline import scope
from ..kernels import hyper_connection as kernels
from ..parallel.moe import _bf16_dot, _bf16_pieces

# What a fresh hyper-connection's biases are the inverses of (`bias_init`).
INIT_READ, INIT_OTHERS = 0.99, 0.01     # H_pre: the stream read, the others
INIT_OFF_DIAGONAL = -8.0                # H~_res off the diagonal (0 on it)
INIT_ALPHA = 0.01


def _slabs(s3, axis: int):
    """Three slabs side by side along ``axis`` added up, the smallest
    first."""
    first, second, third = jnp.split(s3, 3, axis=axis)
    return (third + second) + first


@jax.custom_vjp
def _phi_product(x, phi):
    """``(x @ phi)^T``: ``x [t, m]`` times ``phi [m, k]`` in fp32, to ``[k,
    t]`` in fp32, the tokens minor.  Over bf16 rows one bf16 pass against the
    three pieces of ``phi`` (``[m, 3 k]``, 72 columns of the MXU's 128);
    over any other rows the highest precision."""
    return _phi_product_fwd(x, phi)[0]


def _phi_product_fwd(x, phi):
    if x.dtype == jnp.bfloat16:
        out = _slabs(_bf16_dot(_bf16_pieces(phi, 1), x, ((0,), (1,))), 0)
    else:
        out = lax.dot_general(phi, x, (((0,), (1,)), ((), ())),
                              precision=lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    return out, (x, phi)


def _dphi(x, u3):
    """``x^T [u1 ; u2 ; u3]^T``: bf16 rows ``x [t, m]`` against the three
    pieces ``[3 k, t]`` of a cotangent, one pass, to ``[m, k]``."""
    return _slabs(_bf16_dot(x, u3, ((0,), (1,))), 1)


def _phi_product_bwd(res, u):
    """``u [k, t]``.  Over bf16 rows: ``dphi = x^T [u1 ; u2 ; u3]^T``, one
    pass over the cotangent's three pieces; ``dx = u^T phi^T`` with both
    operands' two leading pieces laid along the contraction (``u_hi phi_hi +
    u_lo phi_hi + u_hi phi_lo``: one pass 3 k = 72 deep), rounded to the
    rows' dtype as every cotangent of theirs is.  The cotangent stays ``[.,
    t]``, the tokens minor."""
    x, phi = res
    with scope("hc.coeff"):
        if x.dtype != jnp.bfloat16:
            dx = lax.dot_general(u, phi, (((0,), (1,)), ((), ())),
                                 precision=lax.Precision.HIGHEST)
            dphi = lax.dot_general(x, u, (((0,), (1,)), ((), ())),
                                   precision=lax.Precision.HIGHEST)
            return dx.astype(x.dtype), dphi
        k = u.shape[0]
        u3 = _bf16_pieces(u, 0)                                  # [3 k, t]
        dphi = _dphi(x, u3)
        p3 = _bf16_pieces(phi, 1)                                # [m, 3 k]
        u_hi, u_lo = u3[:k], u3[k:2 * k]
        p_hi, p_lo = p3[:, :k], p3[:, k:2 * k]
        dx = _bf16_dot(jnp.concatenate([u_hi, u_lo, u_hi], axis=0),
                       jnp.concatenate([p_hi, p_hi, p_lo], axis=1),
                       ((0,), (1,)))
        return dx.astype(x.dtype), dphi


_phi_product.defvjp(_phi_product_fwd, _phi_product_bwd)


def _pre_activations(streams, phi, bias, alpha, n: int, eps: float):
    """``(z [n (n + 2), b s]``, the product ``x~ phi`` before ``alpha`` and
    ``bias``, the norm's factor ``[b s])`` of ``streams [b, s, n, C]``, in
    fp32 with the tokens minor.  The norm has no scale of its own, so its
    factor, one a token, moves behind the product: ``x~ phi = (vec(X) phi) /
    rms``."""
    flat = streams.reshape(streams.shape[0] * streams.shape[1], -1)
    mean2 = jnp.mean(jnp.square(flat.astype(jnp.float32)), axis=-1)
    factor = lax.rsqrt(mean2 + eps)
    product = _phi_product(flat, phi) * factor[None]             # [k, t]
    a = jnp.repeat(alpha, np.asarray([n, n, n * n]))
    return a[:, None] * product + bias[:, None], product, factor


def _gates(z, n: int, clamp: float):
    """``(H_pre [n, b, s], H_post [n, b, s], H~_res clipped [n, n, b, s])``
    of the pre-activations ``z [n (n + 2), b, s]``."""
    pre = jax.nn.sigmoid(z[:n])
    post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    res = jnp.clip(z[2 * n:], -clamp, clamp).reshape((n, n) + z.shape[1:])
    return pre, post, res


def coefficients(streams, phi, bias, alpha, n: int, eps: float, clamp: float):
    """``(H_pre [n, b, s], H_post [n, b, s], H~_res clipped [n, n, b, s])``
    of ``streams [b, s, n, C]``, in fp32 with the tokens minor."""
    z = _pre_activations(streams, phi, bias, alpha, n, eps)[0]
    return _gates(z.reshape((-1,) + streams.shape[:2]), n, clamp)


def _iterations(a, iters: int, eps: float):
    """``SK(a)``, ``a [n, n, ...]`` (row, column, tokens): sums over a
    leading axis and products, unrolled."""
    m = jnp.exp(a)
    for _ in range(iters):
        m = m * (1.0 / (jnp.sum(m, axis=0, keepdims=True) + eps))  # columns
        m = m * (1.0 / (jnp.sum(m, axis=1, keepdims=True) + eps))  # rows
    return m


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def sinkhorn(a, iters: int, eps: float):
    """The doubly stochastic ``H_res [n, n, ...]`` of the clipped logits
    ``a``: ``exp``, then ``iters`` times every column over its sum + ``eps``
    and every row over its sum + ``eps``.  The backward pass runs the
    iterations again from ``a``, the one array kept."""
    return _iterations(a, iters, eps)


def _sinkhorn_fwd(a, iters, eps):
    return _iterations(a, iters, eps), a


def _sinkhorn_bwd(iters, eps, a, g):
    with scope("hc.sinkhorn"):
        return jax.vjp(lambda x: _iterations(x, iters, eps), a)[1](g)


sinkhorn.defvjp(_sinkhorn_fwd, _sinkhorn_bwd)


def deviation(res):
    """``max |sum - 1|`` over the rows and columns of ``res [n, n, ...]`` and
    over the tokens; no gradient."""
    res = lax.stop_gradient(res)
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)))


def mix_down(pre, streams, dtype):
    """``u = H_pre X``: ``pre [n, b, s]``, ``streams [b, s, n, C]`` -> ``[b,
    s, C]`` in ``dtype``, summed in fp32."""
    u = functools.reduce(jnp.add, [
        pre[j][..., None] * streams[:, :, j].astype(jnp.float32)
        for j in range(streams.shape[2])])
    return u.astype(dtype)


def mix_back(res, post, streams, y):
    """``X' = H_res X + H_post^T y``: ``res [n, n, b, s]``, ``post [n, b,
    s]``, ``y [b, s, C]`` -> ``[b, s, n, C]`` in the streams' dtype, summed
    in fp32."""
    n = streams.shape[2]
    x = [streams[:, :, j].astype(jnp.float32) for j in range(n)]
    y = y.astype(jnp.float32)
    out = [functools.reduce(jnp.add, [res[i, j][..., None] * x[j]
                                      for j in range(n)])
           + post[i][..., None] * y for i in range(n)]
    return jnp.stack(out, axis=2).astype(streams.dtype)


def _tokens(x, rows: int):
    """``[..., b, s]`` or ``[b, s, ...]`` with the two token axes as one:
    ``rows`` says how many axes stand before them."""
    return x.reshape(x.shape[:rows] + (-1,) + x.shape[rows + 2:])


def _tokens_minor(x):
    """``[b, s, ..., C] -> [..., C, b s]``, as the kernels take the streams
    (and ``y``, ``du``) and as the compiled step holds them: a bitcast
    there."""
    return jnp.moveaxis(_tokens(x, 0), 0, -1)


def _tokens_major(x, shape):
    """:func:`_tokens_minor` back: ``[..., C, b s] -> [b, s, ..., C]``."""
    return jnp.moveaxis(x, -1, 0).reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _pre_side(streams, phi, bias, alpha, n: int, eps: float, interpret: bool):
    """``(u [b, s, C], z [n (n + 2), b, s], streams)``: the flattened norm,
    the product with ``phi``, ``alpha`` and ``bias``, ``H_pre``'s sigmoid and
    the mix down, whose backward pass is one kernel
    (``kernels/hyper_connection.py::pre_bwd``).  The streams are an output
    too: who reads them there and not from the argument (``_mix_back``) sends
    its cotangent into this function's backward pass, the only writer of
    ``dX``, where autodiff would add two cotangents up in a pass of its
    own."""
    return _pre_side_fwd(streams, phi, bias, alpha, n, eps, interpret)[0]


def _pre_side_fwd(streams, phi, bias, alpha, n, eps, interpret):
    with scope("hc.coeff"):
        z, product, factor = _pre_activations(streams, phi, bias, alpha, n,
                                              eps)
        z = z.reshape((-1,) + streams.shape[:2])
        pre = jax.nn.sigmoid(z[:n])
    with scope("hc.pre"):
        u = mix_down(pre, streams, streams.dtype)
    return (u, z, streams), (streams, phi, alpha, pre, product, factor)


def _pre_bwd_rows(phi, alpha, pre, product, factor, dz, n: int):
    """What a token has of ``_pre_side``'s backward pass before ``dH_pre`` is
    known, as ``kernels.pre_bwd`` takes it: the rows ``(q [k, t], H_pre, s,
    v [n, t], c0 [1, t])`` and ``phi``'s pieces hi, hi, lo ``[m, 3 k]``.
    With ``w = vec(X) phi``, ``r`` the norm's factor and ``z = a w r +
    bias``: ``q = a r dz`` (``dw`` but for its ``pre`` rows' ``s dH_pre``,
    ``s`` the sigmoid's slope times ``a r``), and the norm's term of ``dX``
    is ``(sum dw v) vec(X)`` with ``v = -(r / n C) w r``: ``c0 = sum q v``."""
    k, width = phi.shape[1], phi.shape[0]
    a = jnp.repeat(alpha, np.asarray([n, n, n * n]))[:, None] * factor[None]
    q = a * dz
    v = (-1.0 / width) * factor[None] * product
    p = _bf16_pieces(phi, 1)
    return ((q, pre, a[:n] * pre * (1.0 - pre), v[:n],
             jnp.sum(q * v, axis=0, keepdims=True)),
            jnp.concatenate([p[:, :k], p[:, :k], p[:, k:2 * k]], axis=1))


def _pre_side_bwd(n, eps, interpret, kept, cotangents):
    """``dX = dX~ + H_pre du + phi dw + (sum dw v) vec(X)`` in the kernel
    (:func:`_pre_bwd_rows`), which returns the ``dH_pre`` that closes ``dz``
    for ``dbias``, ``dalpha`` and ``dphi``."""
    streams, phi, alpha, pre, product, factor = kept
    du, dz, dxt = cotangents
    pre, dz = _tokens(pre, 1), _tokens(dz, 1)
    with scope("hc.pre"):
        dx, dpre = kernels.pre_bwd(
            _tokens_minor(du), _tokens_minor(streams), _tokens_minor(dxt),
            *_pre_bwd_rows(phi, alpha, pre, product, factor, dz, n),
            interpret=interpret)
    with scope("hc.coeff"):
        dz = dz.at[:n].add(pre * (1.0 - pre) * dpre)
        a = jnp.repeat(alpha, np.asarray([n, n, n * n]))[:, None]
        dalpha = jnp.sum(dz * product, axis=1)
        dalpha = jnp.stack([jnp.sum(dalpha[:n]), jnp.sum(dalpha[n:2 * n]),
                            jnp.sum(dalpha[2 * n:])])
        # The product's own rule for phi, over the closed cotangent.
        flat = _tokens(streams, 0).reshape(-1, phi.shape[0])
        dphi = _dphi(flat, _bf16_pieces(a * factor[None] * dz, 0))
    return (_tokens_major(dx, streams.shape), dphi, jnp.sum(dz, axis=1),
            dalpha)


_pre_side.defvjp(_pre_side_fwd, _pre_side_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _mix_back(res, post, streams, y, interpret: bool):
    """:func:`mix_back`, whose backward pass is one kernel
    (``kernels/hyper_connection.py::post_bwd``)."""
    return mix_back(res, post, streams, y)


def _mix_back_fwd(res, post, streams, y, interpret):
    return mix_back(res, post, streams, y), (res, post, streams, y)


def _mix_back_bwd(interpret, kept, g):
    res, post, streams, y = kept
    with scope("hc.post"):
        dy, dxt, dres, dpost = kernels.post_bwd(
            _tokens_minor(g), _tokens_minor(streams), _tokens_minor(y),
            _tokens(res, 2), _tokens(post, 1), interpret=interpret)
    return (dres.reshape(res.shape), dpost.reshape(post.shape),
            _tokens_major(dxt, streams.shape), _tokens_major(dy, y.shape))


_mix_back.defvjp(_mix_back_fwd, _mix_back_bwd)


def reference(cfg, streams, phi, bias, alpha):
    """``streams [b, s, n, C] -> (u [b, s, C], back, H_res)`` in
    ``jax.numpy`` under plain autodiff: the one form of a hyper-connection,
    and what :func:`connect` is off the TPU, in float32 and for the shapes
    ``kernels.takes`` refuses."""
    n = cfg.hc_mult
    with scope("hc.coeff"):
        pre, post, logits = coefficients(
            streams, phi, bias, alpha, n, cfg.norm_eps, cfg.hc_res_clamp)
    with scope("hc.sinkhorn"):
        res = sinkhorn(logits, cfg.hc_sinkhorn_iters, cfg.hc_eps)
    with scope("hc.pre"):
        u = mix_down(pre, streams, cfg.dtype)

    def back(y):
        with scope("hc.post"):
            return mix_back(res, post, streams, y)

    return u, back, res


def connect(cfg, streams, phi, bias, alpha, *, interpret: bool = False):
    """:func:`reference` with the same forward operations and, on a TPU (or
    with ``interpret``) for the shapes ``kernels.takes`` takes, the backward
    pass of the stream-sized work in ``kernels/hyper_connection.py``'s two
    kernels: :func:`_pre_side` and :func:`_mix_back` carry them, the
    sigmoids, the clip and :func:`sinkhorn` between the two as they
    were."""
    b, s, n, c = streams.shape
    if not ((interpret or jax.default_backend() == "tpu")
            and streams.dtype == jnp.dtype(cfg.dtype)
            and kernels.takes(n, c, b * s, streams.dtype)):
        return reference(cfg, streams, phi, bias, alpha)
    u, z, streams = _pre_side(streams, phi, bias, alpha, n, cfg.norm_eps,
                              interpret)
    with scope("hc.coeff"):
        _, post, logits = _gates(z, n, cfg.hc_res_clamp)
    with scope("hc.sinkhorn"):
        res = sinkhorn(logits, cfg.hc_sinkhorn_iters, cfg.hc_eps)

    def back(y):
        with scope("hc.post"):
            return _mix_back(res, post, streams, y, interpret)

    return u, back, res


def fan_out(x, n: int):
    """The embedding on every stream: ``[b, s, C] -> [b, s, n, C]``."""
    with scope("hc.post"):
        return jnp.broadcast_to(x[:, :, None], x.shape[:2] + (n,)
                                + x.shape[2:])


def fold(streams):
    """The streams' sum, in fp32: ``[b, s, n, C] -> [b, s, C]``."""
    with scope("hc.post"):
        return jnp.sum(streams.astype(jnp.float32), axis=2)


def bias_init(n: int, read: int):
    """A fresh hyper-connection near the pre-norm residual it replaces
    (hyper-connections' rule): stream ``read`` is read, every stream is
    written with 1 and ``H_res`` lies near the identity.  Through the
    inverses of the sigmoid and of the exponential: ``b_pre = logit(0.99)``
    for the stream read and ``logit(0.01)`` for the others (-+4.595),
    ``b_post = 0`` (2 sigmoid(0) = 1), ``b_res`` 0 on the diagonal and -8 off
    it (``exp(-8)`` = 3.4e-4 of a row's weight to each other stream)."""
    logit = lambda p: math.log(p / (1.0 - p))  # noqa: E731

    def init(key, shape, dtype=jnp.float32):
        del key
        pre = jnp.full((n,), logit(INIT_OTHERS)).at[read % n].set(
            logit(INIT_READ))
        res = jnp.where(jnp.eye(n, dtype=bool), 0.0, INIT_OFF_DIAGONAL)
        out = jnp.concatenate([pre, jnp.zeros((n,)), res.ravel()])
        return out.astype(dtype).reshape(shape)

    return init


class HyperConnection(nn.Module):
    """``streams [b, s, n, C] -> (u [b, s, C], back)``: the sublayer's input
    and the function that takes the sublayer's output ``y [b, s, C]`` to the
    new streams.  ``read``: the stream a fresh connection reads."""

    cfg: "TransformerConfig"  # noqa: F821
    read: int = 0

    @nn.compact
    def __call__(self, streams):
        cfg = self.cfg
        n, c = cfg.hc_mult, streams.shape[-1]
        if streams.ndim != 4 or streams.shape[2] != n:
            raise ValueError(f"{n} streams, got {streams.shape}")
        k = n * (n + 2)
        phi = self.param("phi", nn.initializers.normal(0.02), (n * c, k),
                         jnp.float32)
        bias = self.param("bias", bias_init(n, self.read), (k,), jnp.float32)
        alpha = self.param("alpha", nn.initializers.constant(INIT_ALPHA),
                           (3,), jnp.float32)
        u, back, res = connect(cfg, streams, phi, bias, alpha)
        with scope("hc.sinkhorn"):
            self.sow("hc", "deviation", deviation(res))
        return u, back


def max_deviation(collection):
    """The largest deviation any sublayer sowed into the ``hc`` collection
    that ``apply(..., mutable=["hc"])`` returns."""
    return functools.reduce(jnp.maximum,
                            jax.tree_util.tree_leaves(collection))
