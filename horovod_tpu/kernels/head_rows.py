"""The per-head row work each side of a delta rule's kernels, on the flat
``[b, s, heads * 128]`` layout: each activation read once and written once a
direction.

A Kimi Delta Attention mixer (``models/kda.py``) has, between its causal
convolution and the rule's kernels (``kernels/kda.py``) and between those and
``out_proj``, element-wise work with one reduction over a head's 128 lanes:

    in front    g = lower * sigmoid(a (f + dt_bias))        fp32, a channel
                q = q / |q| * scale,   k = k / |k|          over a head
    behind      y = o / rms(o) * w * sigmoid(z)             over a head

Left to XLA that was fp32 rows written and read between fusions and a
physical copy each time the flat ``[s, 4096]`` became ``[s, 32, 128]`` and
back (under the chip's (8, 128) tiles the two views are different arrays):
106.5 ms of Ling-3.0-flash-VL's step of 558.7 at five times the bytes the
work needs (``PERF.md`` section 6, PR 67).  Here a head is a lane group of the
flat row: nothing is reshaped, the rule's kernels take and give the same
layout, and no head-major array stands between the convolution and
``out_proj``.

**In front of the rule** (:func:`gate`).  The forward kernel reads ``q`` and
``k`` where they lie in the convolution's output ``[b, s, 3 * inner]`` and
``f`` where it lies in the input projection's row (column blocks by the index
map, as ``causal_conv``'s ``within=`` reads that row: no copy of a slice in
front) and writes ``q`` and ``k`` in the rows' dtype and ``g`` in fp32.  ``a``
(``exp(A_log)`` spread to a row ``[1, inner]``) and ``dt_bias [1, inner]`` are
the caller's; their cotangents come back as rows, and XLA's own transpose of
the spread sums ``a``'s a head.  The backward kernel reads the same three and
the rule's ``dq``, ``dk`` and ``dg``, computes the two sums a head and the
sigmoid again and writes ``dq``, ``dk``, ``df`` and the two rows' partial sums
a grid step.  Residuals: the two arrays as they already live.

**Behind the rule** (:func:`norm`).  The forward kernel reads ``o`` as
``hvd_kda_fwd`` writes it and ``z`` in the projection's row and writes ``y``
as ``out_proj`` contracts it; the backward kernel reads ``o``, ``z`` and
``dy`` and writes ``do``, ``dz`` and the partial sums of ``w``'s cotangent.

**The sum over a head's lanes** is the XLU's lane reduction of each ``[tile,
128]``, in fp32: it keeps the HBM's pace (the gate's kernels run at 650 and
684 GB/s over their bytes, the norm's at 582 and 659), and a product with a
block of ones on the MXU over the terms' bf16 pieces, tried beside it, read
the same in the gate's kernels and 4 to 9% slower in the norm's
(``benchmarks/results/head_rows_sweep_pr67.jsonl``), so it left the tree.

**Precision**: what the ``jax.numpy`` lines of ``models/kda.py`` do: fp32
inside, the sums over a head in fp32, ``1e-6`` and ``eps`` where they are,
``q``, ``k``, ``y`` and their cotangents in the rows' dtype, ``g`` and ``dg``
in fp32.  :func:`gate_reference` and :func:`norm_reference` are those lines
on the flat operands (the heads as an axis, so with XLA's copies).

On the device's op line the calls are the four ``*_NAME``\\ s, none under the
rule's ``hvd_kda_`` (``chip_bench/metrics/head_rows_ms_step.json``).  Pallas
is imported where a kernel is built; each direction is one jitted function,
traced once a process and shape whatever the number of layers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

GATE_FWD_NAME = "hvd_head_rows_gate_fwd"
GATE_BWD_NAME = "hvd_head_rows_gate_bwd"
NORM_FWD_NAME = "hvd_head_rows_norm_fwd"
NORM_BWD_NAME = "hvd_head_rows_norm_bwd"
OP_LINE_NAMES = r"^hvd_head_rows_"

_LANES = 128
_SUBLANES = 8
L2_EPS = 1e-6               # ``models/gated_delta.py::_l2_normed``'s
# Positions and columns a grid step.  Measured on a v5e, a kernel alone at
# [8192, 4096] by the device's op line (PERF.md section 6, PR 67;
# ``benchmarks/results/head_rows_sweep_pr67.jsonl``): the four kernels' 36
# calls of a step 21.4 to 23.0 ms over seven blocks from 256 x 512 to 1024 x
# 512 and 128 x 4096, 21.8 at this one, ``causal_conv``'s.
TILE = 512
COLS = 512
_VMEM_LIMIT = 64 * 2 ** 20


def takes(seq_len: int, head_dim: int, dtype) -> bool:
    """Whether a mixer's rows go through the kernels: on a TPU, bf16 heads of
    one lane group, the positions whole tiles.  Otherwise the caller's
    ``jax.numpy`` lines."""
    return (jax.default_backend() == "tpu"
            and jnp.dtype(dtype) == jnp.bfloat16 and head_dim == _LANES
            and seq_len > 0 and seq_len % TILE == 0)


def _by_head(t):
    return t.reshape(*t.shape[:-1], t.shape[-1] // _LANES, _LANES)


def gate_reference(q, k, f, a, dt_bias, *, scale: float, lower: float):
    """:func:`gate` in ``jax.numpy`` on ``q``, ``k``, ``f [b, s, inner]`` cut
    out of their rows: ``models/kda.py``'s lines, the heads as an axis."""
    def normed(x, times):
        x32 = _by_head(x).astype(jnp.float32)
        r = jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + L2_EPS)
        return (x32 * (r * times)).astype(x.dtype).reshape(x.shape)

    g = lower * jax.nn.sigmoid(a * (f.astype(jnp.float32) + dt_bias))
    return normed(q, scale), normed(k, 1.0), g


def norm_reference(o, z, w, *, eps: float):
    """:func:`norm` in ``jax.numpy`` on ``o`` and ``z [b, s, inner]``."""
    o32 = _by_head(o).astype(jnp.float32)
    o32 = o32 * jax.lax.rsqrt(
        jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
    return (o32 * _by_head(w) * _by_head(jax.nn.sigmoid(
        z.astype(jnp.float32)))).astype(o.dtype).reshape(o.shape)


# -- inside a kernel: one head, ``[tile, 128]`` in fp32 -------------------------


def _head_sum(x):
    """The sum over ``x``'s lanes, ``[tile, 1]``, to multiply a ``[tile,
    128]`` by."""
    return jnp.sum(x, axis=-1, keepdims=True)


def _column_sums(x):
    """``[tile, 128]`` -> ``[8, 128]`` whose rows add up to the sum down the
    tile: whole vregs added, the last eight rows left to the caller."""
    return jnp.sum(x.reshape(-1, _SUBLANES, _LANES), axis=0)


def _l2(x):
    """``x`` in fp32 and ``rsqrt(sum x32^2 + 1e-6)`` over its lanes."""
    x32 = x.astype(jnp.float32)
    return x32, jax.lax.rsqrt(_head_sum(x32 * x32) + L2_EPS)


def _l2_normed(x, scale: float):
    """``models/gated_delta.py::_l2_normed`` on one head: ``x32 * (r *
    scale)`` in ``x``'s dtype."""
    x32, r = _l2(x)
    return (x32 * (r * scale)).astype(x.dtype)


def _l2_normed_bwd(x, dy, scale: float):
    """The cotangent of ``x`` under :func:`_l2_normed`: ``r scale (dy - x32
    r^2 sum(dy x32))``."""
    x32, r = _l2(x)
    dy = dy.astype(jnp.float32)
    along = _head_sum(dy * x32)
    return ((r * scale) * (dy - x32 * (r * r * along))).astype(x.dtype)


def _heads(ref):
    return [slice(h * _LANES, (h + 1) * _LANES)
            for h in range(ref.shape[-1] // _LANES)]


def _gate_fwd_kernel(a_ref, dt_ref, q_ref, k_ref, f_ref, qo_ref, ko_ref,
                     g_ref, *, scale: float, lower: float):
    """One tile of positions of one block of heads: ``a`` and ``dt_bias [1,
    cols]``, ``q``, ``k``, ``f [tile, cols]``."""
    for at in _heads(q_ref):
        qo_ref[:, at] = _l2_normed(q_ref[:, at], scale)
        ko_ref[:, at] = _l2_normed(k_ref[:, at], 1.0)
        g_ref[:, at] = lower * jax.nn.sigmoid(
            a_ref[:, at] * (f_ref[:, at].astype(jnp.float32) + dt_ref[:, at]))


def _gate_bwd_kernel(a_ref, dt_ref, q_ref, k_ref, f_ref, dq_ref, dk_ref,
                     dg_ref, dqo_ref, dko_ref, dfo_ref, da_ref, ddt_ref, *,
                     scale: float, lower: float):
    """The same tile's cotangents; ``da`` and ``ddt`` are this grid step's
    ``[8, cols]``, whose rows and steps the caller adds up."""
    for at in _heads(q_ref):
        dqo_ref[:, at] = _l2_normed_bwd(q_ref[:, at], dq_ref[:, at], scale)
        dko_ref[:, at] = _l2_normed_bwd(k_ref[:, at], dk_ref[:, at], 1.0)
        a = a_ref[:, at]
        shifted = f_ref[:, at].astype(jnp.float32) + dt_ref[:, at]
        sig = jax.nn.sigmoid(a * shifted)
        du = dg_ref[:, at] * (lower * (sig * (1.0 - sig)))
        dfo_ref[:, at] = (du * a).astype(dfo_ref.dtype)
        da_ref[:, at] = _column_sums(du * shifted)
        ddt_ref[:, at] = _column_sums(du * a)


def _normed(o, eps: float):
    """``o32 * rsqrt(mean(o32^2) + eps)`` and the root's reciprocal."""
    o32 = o.astype(jnp.float32)
    r = jax.lax.rsqrt(_head_sum(o32 * o32) / _LANES + eps)
    return o32 * r, r


def _norm_fwd_kernel(w_ref, o_ref, z_ref, y_ref, *, eps: float):
    for at in _heads(o_ref):
        n, _ = _normed(o_ref[:, at], eps)
        y_ref[:, at] = (n * w_ref[:, at] * jax.nn.sigmoid(
            z_ref[:, at].astype(jnp.float32))).astype(y_ref.dtype)


def _norm_bwd_kernel(w_ref, o_ref, z_ref, dy_ref, do_ref, dz_ref, dw_ref, *,
                     eps: float):
    for at in _heads(o_ref):
        n, r = _normed(o_ref[:, at], eps)
        w = w_ref[:, at]
        sig = jax.nn.sigmoid(z_ref[:, at].astype(jnp.float32))
        dy = dy_ref[:, at].astype(jnp.float32)
        gated = dy * sig
        dz_ref[:, at] = (dy * (n * w) * (sig * (1.0 - sig))) \
            .astype(dz_ref.dtype)
        dw_ref[:, at] = _column_sums(gated * n)
        dn = gated * w
        along = _head_sum(dn * n) / _LANES
        do_ref[:, at] = (r * (dn - n * along)).astype(do_ref.dtype)


# -- the calls ----------------------------------------------------------------


def _columns(inner: int, cols: int) -> int:
    """Columns a grid step: the widest of ``cols``, its half and so on down
    to a head that divides ``inner`` (so every operand's first column, a
    multiple of ``inner``, is a whole block)."""
    while inner % cols:
        cols //= 2
    return cols


def _specs(tile: int, cols: int):
    """A grid step (sequence, tile, column block ``j``): a ``[tile, cols]``
    block of a ``[b, s, width]`` operand ``first`` blocks into its row; a
    row's ``[1, cols]``; a step's partial sums ``[8, cols]``."""
    import jax.experimental.pallas as pl

    def rows(first: int = 0):
        return pl.BlockSpec((None, tile, cols),
                            lambda i, t, j: (i, t, first + j))

    row = pl.BlockSpec((1, cols), lambda i, t, j: (0, j))
    sums = pl.BlockSpec((None, None, _SUBLANES, cols),
                        lambda i, t, j: (i, t, 0, j))
    return rows, row, sums


def _call(kernel, name, grid, operands, in_specs, out_specs, out_shape, *,
          like, flops: int, transcendentals: int, interpret: bool):
    """``out_shape``: (shape, dtype) pairs, each varying over a mesh as the
    activation ``like`` does; ``flops`` and ``transcendentals`` an
    element."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    vma = jax.typeof(like).vma
    out_shape = [jax.ShapeDtypeStruct(shape, dtype, vma=vma)
                 for shape, dtype in out_shape]
    steps = math.prod(grid)
    elements = steps * math.prod(out_specs[0].block_shape[-2:])

    def moved(spec, x):
        """The bytes the grid moves through one operand's blocks."""
        return steps * math.prod(spec.block_shape[-2:]) \
            * jnp.dtype(x.dtype).itemsize

    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=flops * elements,
            transcendentals=transcendentals * elements,
            bytes_accessed=sum(map(moved, in_specs, operands))
            + sum(map(moved, out_specs, out_shape))),
        name=name, interpret=interpret)(*operands)


_GATE = ("f_at", "scale", "lower", "tile", "cols", "interpret")
_NORM = ("z_at", "eps", "tile", "cols", "interpret")


def _grid(conv, inner: int, tile: int, cols: int):
    b, s, _ = conv.shape
    cols = _columns(inner, cols)
    return (b, s // tile, inner // cols), cols


@functools.partial(jax.jit, static_argnames=_GATE)
def _gate_forward(conv, row, a, dt_bias, *, f_at: int, scale: float,
                  lower: float, tile: int, cols: int, interpret: bool):
    """-> ``(q, k, g)``, ``[b, s, inner]`` each."""
    inner = a.shape[1]
    grid, cols = _grid(conv, inner, tile, cols)
    rows, vector, _ = _specs(tile, cols)
    flat = (*conv.shape[:2], inner)
    return _call(
        functools.partial(_gate_fwd_kernel, scale=scale, lower=lower),
        GATE_FWD_NAME, grid, (a, dt_bias, conv, conv, row),
        [vector, vector, rows(), rows(inner // cols), rows(f_at // cols)],
        [rows(), rows(), rows()],
        [(flat, conv.dtype), (flat, conv.dtype), (flat, a.dtype)],
        like=conv, flops=16, transcendentals=4, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_GATE)
def _gate_backward(conv, row, a, dt_bias, dq, dk, dg, *, f_at: int,
                   scale: float, lower: float, tile: int, cols: int,
                   interpret: bool):
    """-> ``(dq, dk, df [b, s, inner], da, d dt_bias [b, tiles, 8,
    inner])``."""
    inner = a.shape[1]
    grid, cols = _grid(conv, inner, tile, cols)
    rows, vector, partial = _specs(tile, cols)
    flat = (*conv.shape[:2], inner)
    parts = (grid[0], grid[1], _SUBLANES, inner)
    return _call(
        functools.partial(_gate_bwd_kernel, scale=scale, lower=lower),
        GATE_BWD_NAME, grid, (a, dt_bias, conv, conv, row, dq, dk, dg),
        [vector, vector, rows(), rows(inner // cols), rows(f_at // cols),
         rows(), rows(), rows()],
        [rows(), rows(), rows(), partial, partial],
        [(flat, conv.dtype), (flat, conv.dtype), (flat, row.dtype),
         (parts, a.dtype), (parts, dt_bias.dtype)],
        like=conv, flops=40, transcendentals=4, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_NORM)
def _norm_forward(o, row, w, *, z_at: int, eps: float, tile: int, cols: int,
                  interpret: bool):
    grid, cols = _grid(o, o.shape[2], tile, cols)
    rows, vector, _ = _specs(tile, cols)
    return _call(
        functools.partial(_norm_fwd_kernel, eps=eps),
        NORM_FWD_NAME, grid, (w, o, row),
        [vector, rows(), rows(z_at // cols)], [rows()], [(o.shape, o.dtype)],
        like=o, flops=8, transcendentals=3, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=_NORM)
def _norm_backward(o, row, w, dy, *, z_at: int, eps: float, tile: int,
                   cols: int, interpret: bool):
    """-> ``(do, dz [b, s, inner], dw [b, tiles, 8, inner])``."""
    inner = o.shape[2]
    grid, cols = _grid(o, inner, tile, cols)
    rows, vector, partial = _specs(tile, cols)
    return _call(
        functools.partial(_norm_bwd_kernel, eps=eps),
        NORM_BWD_NAME, grid, (w, o, row, dy),
        [vector, rows(), rows(z_at // cols), rows()],
        [rows(), rows(), partial],
        [(o.shape, o.dtype), (o.shape, row.dtype),
         ((grid[0], grid[1], _SUBLANES, inner), w.dtype)],
        like=o, flops=24, transcendentals=3, interpret=interpret)


def _within(d, row, at: int):
    """What a slice's own transpose would hand the row's other columns."""
    return jnp.pad(d, ((0, 0), (0, 0),
                       (at, row.shape[2] - at - d.shape[2])))


def _row_sum(parts):
    return jnp.sum(parts, axis=(0, 1, 2))[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gate(conv, row, a, dt_bias, static):
    return tuple(_gate_forward(conv, row, a, dt_bias, **dict(static)))


def _gate_fwd(conv, row, a, dt_bias, static):
    return (tuple(_gate_forward(conv, row, a, dt_bias, **dict(static))),
            (conv, row, a, dt_bias))


def _gate_bwd(static, kept, cotangents):
    conv, row = kept[:2]
    dq, dk, df, da, ddt = _gate_backward(*kept, *cotangents, **dict(static))
    return (_within(jnp.concatenate([dq, dk], axis=-1), conv, 0),
            _within(df, row, dict(static)["f_at"]), _row_sum(da),
            _row_sum(ddt))


_gate.defvjp(_gate_fwd, _gate_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _norm(o, row, w, static):
    return _norm_forward(o, row, w, **dict(static))


def _norm_fwd(o, row, w, static):
    return _norm_forward(o, row, w, **dict(static)), (o, row, w)


def _norm_bwd(static, kept, dy):
    do, dz, dw = _norm_backward(*kept, dy, **dict(static))
    return do, _within(dz, kept[1], dict(static)["z_at"]), _row_sum(dw)


_norm.defvjp(_norm_fwd, _norm_bwd)


def _checked(s: int, inner: int, at: int, row, tile: int):
    if inner % _LANES or at % inner or at + inner > row.shape[2] \
            or s % tile or tile % _SUBLANES:
        raise ValueError(f"heads of {_LANES} in {inner} columns from {at} of "
                         f"{row.shape}, {s} positions in tiles of {tile}")


def gate(conv, row, a, dt_bias, *, f_at: int, scale: float, lower: float,
         tile: int = TILE, cols: int = COLS, interpret: bool = False):
    """``(q / |q| * scale, k / |k|, lower * sigmoid(a (f + dt_bias)))``, ``[b,
    s, inner]`` each, the first two in ``conv``'s dtype and the third in
    fp32, from ``q`` and ``k`` where they lie in ``conv [b, s, >= 2 * inner]``
    (its first and second ``inner`` columns), ``f`` where it lies in ``row [b,
    s, width]`` (``inner`` columns from ``f_at``, a multiple of ``inner``)
    and the rows ``a`` and ``dt_bias [1, inner]`` in fp32; the norms over
    each lane group of 128.  Differentiable in all four.  The kernels
    whatever the backend (``interpret``: in interpret mode); the caller asks
    :func:`takes`."""
    inner = a.shape[1]
    _checked(conv.shape[1], inner, f_at, row, tile)
    if a.shape != dt_bias.shape or conv.shape[2] < 2 * inner \
            or conv.shape[:2] != row.shape[:2]:
        raise ValueError(f"conv {conv.shape}, row {row.shape}, a {a.shape}, "
                         f"dt_bias {dt_bias.shape}")
    return _gate(conv, row, a, dt_bias, (
        ("f_at", f_at), ("scale", scale), ("lower", lower), ("tile", tile),
        ("cols", cols), ("interpret", interpret)))


def norm(o, row, w, *, z_at: int, eps: float, tile: int = TILE,
         cols: int = COLS, interpret: bool = False):
    """``o * rsqrt(mean(o^2) + eps) * w * sigmoid(z)`` over each lane group
    of 128, ``[b, s, inner]`` in ``o``'s dtype, from ``o [b, s, inner]``, ``z``
    where it lies in ``row [b, s, width]`` (``inner`` columns from ``z_at``)
    and the row ``w [1, inner]`` in fp32.  Differentiable in all three.  The
    kernels whatever the backend; the caller asks :func:`takes`."""
    _checked(o.shape[1], o.shape[2], z_at, row, tile)
    if w.shape != (1, o.shape[2]) or o.shape[:2] != row.shape[:2]:
        raise ValueError(f"o {o.shape}, row {row.shape}, w {w.shape}")
    return _norm(o, row, w, (
        ("z_at", z_at), ("eps", eps), ("tile", tile), ("cols", cols),
        ("interpret", interpret)))
