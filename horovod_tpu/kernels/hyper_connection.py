"""The backward pass of a hyper-connection's stream-sized work in two kernels
that read the streams once each (``models/hyper_connections.py`` has the
mathematics and the forward, which stays ``jax.numpy``).

A token's streams ``X [n, C]``, the sublayer's output ``y [C]``, ``g = dX'``:

    post_bwd    dy       = sum_i H_post[i] g_i              in front of the
                dX~_j    = sum_i H_res[i, j] g_i            sublayer's own
                dH_res   = sum_c g_i x_j     [n, n]         backward pass
                dH_post  = sum_c g_i y       [n]
    pre_bwd     dH_pre_j = sum_c du x_j                     behind it
                dw       = q + [s_j dH_pre_j ; 0]           the cotangent of
                                                            vec(X) phi, closed
                dX_j     = dX~_j + H_pre[j] du + (phi dw)_j
                           + (c0 + sum_j s_j dH_pre_j v_j) x_j

``q [k]``, ``s``, ``v [n]`` and ``c0`` are a token's numbers that the caller
makes of the pre-activations' cotangent, the norm's factor and the sigmoid's
slope (``models/hyper_connections.py::_pre_side_bwd``): the kernel closes the
``pre`` rows with the ``dH_pre`` only it knows, and is the one writer of
``dX``.  Left to XLA the first was twenty multiply-reduce passes over the
streams and the second an fp32 ``[tokens, n C]`` and three passes more
(``PERF.md`` §6, PR 59).  ``dphi = vec(X)^T dw`` stays the caller's product
over the closed ``dw`` it makes of the ``dH_pre`` returned: inside
``pre_bwd`` it cost the kernel 0.02 ms and the compiled step 0.29 GiB of
temporaries, over what the cell's file states.

**Layout.**  The tokens are the minor dimension of everything, the streams
too: they come ``[n, C, tokens]``, a token a lane and a channel a sublane,
which is how the compiled step holds them (``bf16[1,8192,4,3584]{1,3,2,0}``
in every fusion: beside coefficients ``[k, tokens]`` XLA keeps the streams
turned, so that a coefficient broadcasts down the sublanes; handed ``[tokens,
n C]`` or ``[n, tokens, C]`` both kernels stood behind a ``copy`` of the
streams, 19 a step, ``PERF.md`` §6, PR 59): the caller's transpose is a
bitcast there.  A token's coefficients are rows ``[k, tokens]`` as the
caller has them, the per-token sums leave the same way, and the sums over
the channels are sums down the sublanes: a grid step walks groups of
:data:`_ROWS` channels with one vreg an accumulator and reduces the eight
sublanes once at the end.  ``post_bwd``'s grid is (tokens, channels), its
sums added up over the second; ``pre_bwd`` takes a token block's channels
whole, since ``dH_pre`` has to be complete before ``dX`` can be written.

**Precision**: streams in bf16, every product and sum in fp32, one rounding
at each output; the product against ``phi`` one bf16 pass of the MXU over the
two leading pieces of both operands (``hi hi + lo hi + hi lo``, 72 deep), as
``_phi_product_bwd`` states it.

On the device's op line the calls are :data:`POST_BWD_NAME` and
:data:`PRE_BWD_NAME` (``chip_bench/metrics/hyper_connection_kernel*``).
Pallas is imported where a kernel is built, not with this module, and each
kernel is one jitted function (``kernels/short_conv.py`` says why).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# The calls' names on the device's op line, and what matches both.
POST_BWD_NAME = "hvd_hyper_connection_post_bwd"
PRE_BWD_NAME = "hvd_hyper_connection_pre_bwd"
OP_LINE_NAMES = r"^hvd_hyper_connection"

_LANES = 128
_ROWS = 16            # channels a group: one tile of bf16
_MOST_STREAMS = 4     # the sums are unrolled: n^2 + n accumulators a group
_POST_TOKENS = 512    # tokens a grid step, at most
_PRE_TOKENS = 128
_VMEM_LIMIT = 100 * 2 ** 20


def takes(n: int, c: int, tokens: int, dtype=jnp.bfloat16) -> bool:
    """Whether the kernels take ``n`` streams ``[n, c, tokens]`` of
    ``dtype``; otherwise, and off the TPU, plain autodiff of the
    ``jax.numpy`` forward."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and 1 <= n <= _MOST_STREAMS
            and c % _LANES == 0 and tokens > 0 and tokens % _LANES == 0)


def _sum(terms):
    """Pairwise, so that the terms are not chained through one sum."""
    while len(terms) > 1:
        terms = [a + b for a, b in zip(terms[::2], terms[1::2])] \
            + terms[len(terms) // 2 * 2:]
    return terms[0]


def _most(size: int, sizes) -> int:
    """The largest of ``sizes`` that divides ``size``."""
    return next(s for s in sizes if size % s == 0)


def _rows_of(rows, tokens: int):
    """``[k, tokens]`` rows (a list, joined) in fp32 with zeros behind, to a
    multiple of 8 rows."""
    rows = jnp.concatenate([r.reshape(-1, tokens).astype(jnp.float32)
                            for r in rows])
    return jnp.pad(rows, ((0, -rows.shape[0] % 8), (0, 0)))


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _down(row, rows: int):
    """A token row ``[1, 128]`` on ``rows`` sublanes."""
    return jnp.broadcast_to(row, (rows, row.shape[1]))


def _halves(x):
    """A group's ``[16, 128]`` as the sum of its two ``[8, 128]`` halves:
    what an accumulator of one vreg takes."""
    return x[:_ROWS // 2] + x[_ROWS // 2:]


def _stack(rows, height: int):
    """Token rows ``[1, 128]``, a sublane each from sublane 0 on, as one
    ``[height, 128]`` tile with zeros behind."""
    at = lax.broadcasted_iota(jnp.int32, (height, rows[0].shape[1]), 0)
    tile = jnp.zeros((height, rows[0].shape[1]), jnp.float32)
    for k, row in enumerate(rows):
        tile = jnp.where(at == k, row, tile)
    return tile


def _post_bwd_kernel(h_ref, g_ref, x_ref, y_ref, dy_ref, dxt_ref, sums_ref,
                     *, n: int):
    """One block of channels of one block of tokens: ``h [24, T]``
    (``H_res[i, j]`` on row ``n i + j``, ``H_post[i]`` on row ``n n + i``),
    ``g``, ``x [n, C, T]``, ``y [C, T]``; ``dy``, ``dX~`` out, and the ``n n
    + n`` sums added to ``h``'s rows of a block that stays where it is while
    a token block's channels go by."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    channels, tokens = y_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    for chunk in range(tokens // _LANES):
        lanes = slice(chunk * _LANES, (chunk + 1) * _LANES)
        h = h_ref[:, lanes]
        res = [[_down(h[n * i + j:n * i + j + 1], _ROWS) for j in range(n)]
               for i in range(n)]
        post = [_down(h[n * n + i:n * n + i + 1], _ROWS) for i in range(n)]

        def group(r, sums, lanes=lanes, res=res, post=post):
            at = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
            g = [g_ref[i, at, lanes].astype(f32) for i in range(n)]
            x = [x_ref[j, at, lanes].astype(f32) for j in range(n)]
            y = y_ref[at, lanes].astype(f32)
            dy_ref[at, lanes] = _sum(
                [post[i] * g[i] for i in range(n)]).astype(dy_ref.dtype)
            for j in range(n):
                dxt_ref[j, at, lanes] = _sum(
                    [res[i][j] * g[i] for i in range(n)]
                ).astype(dxt_ref.dtype)
            # Side by side, not chained: n n + n accumulators of one vreg.
            products = [g[i] * x[j] for i in range(n) for j in range(n)] \
                + [g[i] * y for i in range(n)]
            return [s + _halves(p) for s, p in zip(sums, products)]

        sums = lax.fori_loop(
            0, channels // _ROWS, group,
            [jnp.zeros((_ROWS // 2, _LANES), f32)] * (n * n + n))
        sums_ref[:, lanes] += _stack(
            [jnp.sum(s, axis=0, keepdims=True) for s in sums],
            sums_ref.shape[0])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def post_bwd(g, x, y, res, post, *, block: int = _POST_TOKENS,
             interpret: bool = False):
    """``g = dX'``, ``x [n, c, T]``, ``y [c, T]`` in bf16; ``res [n, n, T]``,
    ``post [n, T]`` in fp32 -> ``(dy [c, T], dX~ [n, c, T], dH_res [n, n, T],
    dH_post [n, T])``.  Jitted: traced once a process and lowered once a
    program, whatever the number of sublayers.  ``block``: tokens a grid
    step, at most."""
    import jax.experimental.pallas as pl

    c, tokens = y.shape
    n = post.shape[0]
    block = _most(tokens, [b for b in (512, 256, _LANES) if b <= block])
    channels = _most(c, (512, 256, _LANES))
    h = _rows_of([res, post], tokens)
    vma = jax.typeof(x).vma
    coefficients = pl.BlockSpec((h.shape[0], block), lambda t, k: (0, t))
    stream = pl.BlockSpec((channels, block), lambda t, k: (k, t))
    streams = pl.BlockSpec((n, channels, block), lambda t, k: (0, k, t))
    dy, dxt, sums = pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n),
        grid=(tokens // block, c // channels),
        in_specs=[coefficients, streams, streams, stream],
        out_specs=[stream, streams, coefficients],
        out_shape=[jax.ShapeDtypeStruct((c, tokens), y.dtype, vma=vma),
                   jax.ShapeDtypeStruct((n, c, tokens), x.dtype, vma=vma),
                   jax.ShapeDtypeStruct(h.shape, jnp.float32, vma=vma)],
        # The sums are added up along a token block's channels.
        compiler_params=_params("parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * (2 * n * n + 2 * n) * tokens * c, transcendentals=0,
            bytes_accessed=2 * (3 * n + 2) * tokens * c),
        name=POST_BWD_NAME, interpret=interpret,
    )(h, g, x, y)
    return dy, dxt, sums[:n * n].reshape(n, n, tokens), sums[n * n:n * n + n]


def _two_pieces(w):
    """The two leading bf16 pieces of ``w`` (fp32), each in fp32."""
    hi = w.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (w - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _pre_bwd_kernel(k_ref, du_ref, x_ref, dxt_ref, p_ref, dx_ref, dhp_ref,
                    *, n: int, kk: int):
    """One block of tokens, all channels.  ``k [40, T]``: ``q`` (``kk``
    rows), then ``H_pre``, ``s``, ``v`` (``n`` rows each) and ``c0``; ``du
    [C, T]``, ``x``, ``dX~ [n, C, T]``; ``p [n C, 128]``: ``phi``'s pieces hi,
    hi, lo along the lanes.  First the block's ``dH_pre``, which closes
    ``dw`` and the norm's factor; then ``dX``, some hundred channels of
    every stream at a time, against the one ``dw`` the MXU holds."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    c, tokens = du_ref.shape
    at_hpre, at_s, at_v, at_c0 = kk, kk + n, kk + 2 * n, kk + 3 * n
    most = _most(c, (256, _LANES))

    for chunk in range(tokens // _LANES):
        lanes = slice(chunk * _LANES, (chunk + 1) * _LANES)

        def group(r, sums, lanes=lanes):
            at = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
            du = du_ref[at, lanes].astype(f32)
            return [s + _halves(du * x_ref[j, at, lanes].astype(f32))
                    for j, s in enumerate(sums)]

        sums = lax.fori_loop(0, c // _ROWS, group,
                             [jnp.zeros((_ROWS // 2, _LANES), f32)] * n)
        dhp = [jnp.sum(s, axis=0, keepdims=True) for s in sums]
        dhp_ref[:, lanes] = _stack(dhp, dhp_ref.shape[0])
        k = k_ref[:, lanes]
        closing = [k[at_s + j:at_s + j + 1] * dhp[j] for j in range(n)]
        dw = k[:kk] + _stack(closing, kk)
        factor = _down(k[at_c0:at_c0 + 1] + _sum(
            [closing[j] * k[at_v + j:at_v + j + 1] for j in range(n)]), most)
        hpre = [_down(k[at_hpre + j:at_hpre + j + 1], most) for j in range(n)]
        hi, lo = _two_pieces(dw)
        dw3 = jnp.concatenate(
            [hi, lo, hi, jnp.zeros((_LANES - 3 * kk, _LANES), f32)]
        ).astype(p_ref.dtype)

        def rows(r, carry, lanes=lanes, dw3=dw3, factor=factor, hpre=hpre):
            at = pl.ds(pl.multiple_of(r * most, most), most)
            du = du_ref[at, lanes].astype(f32)
            for j in range(n):
                through_phi = jnp.dot(
                    p_ref[pl.ds(pl.multiple_of(j * c + r * most, most), most),
                          :], dw3, preferred_element_type=f32)
                dx_ref[j, at, lanes] = (
                    (dxt_ref[j, at, lanes].astype(f32) + hpre[j] * du)
                    + (through_phi + factor * x_ref[j, at, lanes].astype(f32))
                ).astype(dx_ref.dtype)
            return carry

        lax.fori_loop(0, c // most, rows, 0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def pre_bwd(du, x, dxt, coeffs, p, *, block: int = _PRE_TOKENS,
            interpret: bool = False):
    """``du [c, T]``, ``x``, ``dX~ [n, c, T]`` in bf16; ``coeffs`` the rows
    ``(q [k, T], H_pre, s, v [n, T], c0 [1, T])`` in fp32 (the module's
    docstring); ``p [n c, 3 k]`` in bf16: ``phi``'s pieces hi, hi, lo ->
    ``(dX [n, c, T], dH_pre [n, T])``.  ``block``: tokens a grid step."""
    import jax.experimental.pallas as pl

    c, tokens = du.shape
    n, kk = coeffs[1].shape[0], coeffs[0].shape[0]
    k = _rows_of(coeffs, tokens)
    vma = jax.typeof(x).vma

    def rows(height):
        return pl.BlockSpec((height, block), lambda t: (0, t))

    streams = pl.BlockSpec((n, c, block), lambda t: (0, 0, t))
    dx, dhp = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, kk=kk),
        grid=(tokens // block,),
        in_specs=[rows(k.shape[0]), rows(c), streams, streams,
                  pl.BlockSpec((n * c, _LANES), lambda t: (0, 0))],
        out_specs=[streams, rows(8)],
        out_shape=[jax.ShapeDtypeStruct((n, c, tokens), x.dtype, vma=vma),
                   jax.ShapeDtypeStruct((8, tokens), jnp.float32, vma=vma)],
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=2 * (3 * kk + 4) * n * tokens * c, transcendentals=0,
            bytes_accessed=2 * (3 * n + 1) * tokens * c),
        name=PRE_BWD_NAME, interpret=interpret,
    )(k, du, x, dxt, jnp.pad(p, ((0, 0), (0, _LANES - p.shape[1]))))
    return dx, dhp[:n]
