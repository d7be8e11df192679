"""The gated short convolution, ``y = C * conv_L(B * x)``, forward and
backward in one kernel each.

LFM2's convolution layers (``models/transformer.py::ShortConv``) project a
position to three vectors ``[B, C, X]``, gate ``X`` by ``B``, run a depthwise
causal convolution of ``L`` taps (3) over the sequence, a filter a channel,
and gate the result by ``C``:

    z_t = B_t * X_t
    c_t = sum_j w[:, j] * z_{t-(L-1)+j}        z = 0 before the sequence
    y_t = C_t * c_t

There is no product on the MXU in it: it is 44 KB of HBM traffic a token and
layer at width 2048, forward and backward, and left to autodiff it keeps
``z`` and ``c`` in fp32 besides (8 KB a token and layer each way) in a step
whose memory is the constraint.  Here the forward kernel reads ``bcx`` once
and writes ``y`` once; the backward kernel reads ``bcx`` and ``dy`` once,
computes ``z`` and ``c`` again, writes ``d_bcx`` once and adds the taps'
gradient up in fp32, one partial sum a sequence.  The residual is ``bcx``
alone.

**How it goes.**  A grid step takes :func:`_tile` positions of one sequence
at the projection's whole width, so the three parts are aligned lane slices
of one block and ``d_bcx`` is one block too; inside, :data:`_COLS` channels
at a time.  The ``L - 1`` rows before a tile come with a second view of the
same array, the :data:`_HALO` rows that end where the tile begins, and count
as zero in a sequence's first tile, so nothing leaks from one sequence of a
batch into the next; the backward pass takes the ``L - 1`` rows behind the
tile of ``dy`` and ``C`` the same way.  A shift along the sequence is a
rotation of the tile's rows with the rows that came round replaced.

**Precision**: ``bcx`` and ``y`` in bf16, the taps in fp32; the gates and the
taps' sums run in fp32 and round once, at the output.  :func:`reference` is
the same function in ``jax.numpy`` (shifted sums), the path off the TPU and
for shapes :func:`takes` refuses.

On the device's op line the calls are :data:`FWD_NAME` and :data:`BWD_NAME`
(``chip_bench/metrics/short_conv_ms_step.json``).  Pallas is imported where a
kernel is built, not with this module.  Each direction is one jitted
function: a model holds the call once a layer, and tracing and lowering a
pallas kernel is host work that a program pays at every start, before the
compile cache is asked (``PERF.md`` §6, PR 35).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# The calls' names on the device's op line, and what matches both.
FWD_NAME = "hvd_short_conv_fwd"
BWD_NAME = "hvd_short_conv_bwd"
OP_LINE_NAMES = r"^hvd_short_conv"

_LANES = 128
_HALO = 16        # rows of bf16 in one tile of HBM: the neighbour's rows
_TAP_ROWS = 8     # the taps lie as rows [8, d] of fp32: at most 8 of them
_COLS = 512       # channels at a time inside a grid step
_MAX_TILE = 256   # positions a grid step
_VMEM_LIMIT = 64 * 2 ** 20


def _tile(seq_len: int, most: int = _MAX_TILE) -> int:
    """Positions a grid step: the largest power of two up to ``most``
    (:data:`_MAX_TILE`) that divides the sequence."""
    tile = most
    while tile > _HALO and seq_len % tile:
        tile //= 2
    return tile


def takes(seq_len: int, d: int, taps: int, dtype=jnp.bfloat16) -> bool:
    """Whether the kernel takes ``bcx [b, seq_len, 3 * d]`` of ``dtype`` and
    ``taps`` taps; otherwise, and off the TPU, :func:`reference`."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and d % _LANES == 0
            and seq_len > 0 and seq_len % _HALO == 0
            and 1 <= taps <= _TAP_ROWS)


def reference(bcx, w):
    """:func:`gated_conv` in ``jax.numpy``: the taps as shifted sums over a
    sequence padded with ``L - 1`` zero rows in front, gates and sums in
    fp32, the result in ``bcx``'s dtype."""
    s, taps = bcx.shape[1], w.shape[1]
    gate_in, gate_out, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    z = jnp.pad(gate_in * x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    c = sum(w[:, j] * z[:, j:j + s] for j in range(taps))
    return (gate_out * c).astype(bcx.dtype)


def _rows_from(ref, lo: int, c0: int, cols: int):
    return ref[:, lo + c0:lo + c0 + cols].astype(jnp.float32)


def _earlier(x, before, k: int):
    """``x[t - k]`` down the rows of a tile ``[tile, cols]``; the first ``k``
    rows are the last ``k`` of ``before [_HALO, cols]``."""
    from jax.experimental.pallas import tpu as pltpu

    if k == 0:
        return x
    out = pltpu.roll(x, k, 0)
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    for r in range(k):
        at = _HALO - k + r
        out = jnp.where(row == r, before[at:at + 1, :], out)
    return out


def _later(x, after, k: int):
    """``x[t + k]`` down the rows of a tile; the last ``k`` rows are the
    first ``k`` of ``after [_HALO, cols]``."""
    from jax.experimental.pallas import tpu as pltpu

    if k == 0:
        return x
    tile = x.shape[0]
    out = pltpu.roll(x, tile - k, 0)
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    for r in range(k):
        out = jnp.where(row == tile - k + r, after[r:r + 1, :], out)
    return out


def _fwd_kernel(w_ref, bcx_ref, before_ref, y_ref, *, d: int, taps: int):
    """One tile of one sequence: the taps ``[8, d]``, ``bcx [tile, 3d]``, the
    ``_HALO`` rows before it, ``y [tile, d]`` out."""
    import jax.experimental.pallas as pl

    first = pl.program_id(1) == 0
    for c0 in range(0, d, min(_COLS, d)):
        cols = min(_COLS, d - c0)
        z = _rows_from(bcx_ref, 0, c0, cols) \
            * _rows_from(bcx_ref, 2 * d, c0, cols)
        z_before = _rows_from(before_ref, 0, c0, cols) \
            * _rows_from(before_ref, 2 * d, c0, cols)
        z_before = jnp.where(first, 0.0, z_before)
        c = None
        for j in range(taps):
            term = w_ref[j:j + 1, c0:c0 + cols] \
                * _earlier(z, z_before, taps - 1 - j)
            c = term if c is None else c + term
        y_ref[:, c0:c0 + cols] = (_rows_from(bcx_ref, d, c0, cols) * c) \
            .astype(y_ref.dtype)


def _bwd_kernel(w_ref, bcx_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                dbcx_ref, dw_ref, *, d: int, taps: int):
    """The same tile's cotangents: ``z`` and ``c`` again, ``d_bcx [tile,
    3d]`` written once, the taps' gradient added to the sequence's ``[8,
    d]`` block, which stays where it is while the tiles of a sequence go
    by."""
    import jax.experimental.pallas as pl

    t = pl.program_id(1)
    first, last = t == 0, t == pl.num_programs(1) - 1

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for c0 in range(0, d, min(_COLS, d)):
        cols = min(_COLS, d - c0)
        gate_in = _rows_from(bcx_ref, 0, c0, cols)
        gate_out = _rows_from(bcx_ref, d, c0, cols)
        x = _rows_from(bcx_ref, 2 * d, c0, cols)
        dy = dy_ref[:, c0:c0 + cols].astype(jnp.float32)
        z = gate_in * x
        z_before = jnp.where(first, 0.0,
                             _rows_from(before_ref, 0, c0, cols)
                             * _rows_from(before_ref, 2 * d, c0, cols))
        dc = dy * gate_out
        dc_after = jnp.where(
            last, 0.0, dy_after_ref[:, c0:c0 + cols].astype(jnp.float32)
            * _rows_from(after_ref, d, c0, cols))
        c = dz = None
        for j in range(taps):
            k = taps - 1 - j
            w = w_ref[j:j + 1, c0:c0 + cols]
            z_k = _earlier(z, z_before, k)
            term, back = w * z_k, w * _later(dc, dc_after, k)
            c, dz = (term, back) if c is None else (c + term, dz + back)
            dw_ref[j:j + 1, c0:c0 + cols] += jnp.sum(dc * z_k, axis=0,
                                                     keepdims=True)
        for lo, value in ((0, dz * x), (d, dy * c), (2 * d, dz * gate_in)):
            dbcx_ref[:, lo + c0:lo + c0 + cols] = value.astype(dbcx_ref.dtype)


def _specs(s: int, d: int, columns: bool = False, most: int = _MAX_TILE):
    """(tile, a ``[b, s, width]`` operand's block by width, the ``_HALO``
    rows before a tile, those behind it, the taps' block ``[8, d]``).

    With ``columns`` (``kernels/causal_conv.py``) the grid is (sequence,
    block of columns, tile) and not (sequence, tile): a block of ``width``
    columns is one of several side by side, counted from its array's block
    ``first``, which is how a kernel reads a window of a wider row where it
    lies, and ``d`` is the taps' share of one such block; tiles of at most
    ``most`` positions."""
    import jax.experimental.pallas as pl

    tile = _tile(s, most)
    per, blocks = tile // _HALO, s // _HALO

    def spec(rows, width, row_of, first):
        if columns:
            return pl.BlockSpec((None, rows, width),
                                lambda i, j, t: (i, row_of(t), first + j))
        return pl.BlockSpec((None, rows, width),
                            lambda i, t: (i, row_of(t), 0))

    def rows(width, first=0):
        return spec(tile, width, lambda t: t, first)

    def before(width, first=0):
        return spec(_HALO, width, lambda t: jnp.maximum(t * per - 1, 0),
                    first)

    def after(width, first=0):
        return spec(_HALO, width,
                    lambda t: jnp.minimum((t + 1) * per, blocks - 1), first)

    taps = pl.BlockSpec((_TAP_ROWS, d), (lambda i, j, t: (0, j)) if columns
                        else (lambda i, t: (0, 0)))
    return tile, rows, before, after, taps


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _tap_rows(w):
    """``w [d, L]`` as rows ``[8, d]`` of fp32, zeros behind the last."""
    d, taps = w.shape
    return jnp.zeros((_TAP_ROWS, d), jnp.float32).at[:taps].set(
        w.astype(jnp.float32).T)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(bcx, w, *, interpret: bool):
    """``bcx [b, s, 3d]``, ``w [d, L]`` -> ``y [b, s, d]``.  Jitted: traced
    once a process and lowered once a program, whatever the number of
    layers."""
    import jax.experimental.pallas as pl

    b, s, width = bcx.shape
    d = width // 3
    tile, rows, before, _, taps = _specs(s, d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, taps=w.shape[1]),
        grid=(b, s // tile),
        in_specs=[taps, rows(width), before(width)],
        out_specs=rows(d),
        out_shape=jax.ShapeDtypeStruct((b, s, d), bcx.dtype,
                                       vma=jax.typeof(bcx).vma),
        compiler_params=_params("parallel", "parallel"),
        cost_estimate=pl.CostEstimate(
            flops=2 * (w.shape[1] + 1) * b * s * d, transcendentals=0,
            bytes_accessed=2 * b * s * (width + d)),
        name=FWD_NAME, interpret=interpret,
    )(_tap_rows(w), bcx, bcx)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(bcx, w, dy, *, interpret: bool):
    """-> ``(d_bcx [b, s, 3d], d_w [d, L] in w's dtype)``."""
    import jax.experimental.pallas as pl

    b, s, width = bcx.shape
    d = width // 3
    tile, rows, before, after, taps = _specs(s, d)
    vma = jax.typeof(bcx).vma
    d_bcx, d_w = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, taps=w.shape[1]),
        grid=(b, s // tile),
        in_specs=[taps, rows(width), before(width), after(width), rows(d),
                  after(d)],
        out_specs=[rows(width),
                   pl.BlockSpec((None, _TAP_ROWS, d), lambda i, t: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype, vma=vma),
                   jax.ShapeDtypeStruct((b, _TAP_ROWS, d), jnp.float32,
                                        vma=vma)],
        # The taps' gradient is added up along a sequence's tiles.
        compiler_params=_params("parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * (3 * w.shape[1] + 4) * b * s * d, transcendentals=0,
            bytes_accessed=2 * b * s * (2 * width + d)),
        name=BWD_NAME, interpret=interpret,
    )(_tap_rows(w), bcx, bcx, bcx, dy, dy)
    return d_bcx, jnp.sum(d_w, axis=0)[:w.shape[1]].T.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gated_conv(bcx, w, interpret):
    return _forward(bcx, w, interpret=interpret)


def _gated_conv_fwd(bcx, w, interpret):
    return _forward(bcx, w, interpret=interpret), (bcx, w)


def _gated_conv_bwd(interpret, kept, dy):
    return _backward(*kept, dy, interpret=interpret)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def gated_conv(bcx, w, *, interpret: bool = False):
    """``C * conv(B * X)`` for ``bcx = [B, C, X]`` ``[b, s, 3d]`` and the
    taps ``w [d, L]``, a filter a channel, causal and never across the
    sequences of a batch: ``[b, s, d]`` in ``bcx``'s dtype.  Differentiable
    in both.  On a TPU (or with ``interpret``) the kernels, for the shapes
    :func:`takes` takes; else :func:`reference`."""
    _, s, width = bcx.shape
    d, taps = w.shape
    if width != 3 * d:
        raise ValueError(f"bcx {bcx.shape} for taps {w.shape}")
    if (interpret or jax.default_backend() == "tpu") \
            and takes(s, d, taps, bcx.dtype):
        return _gated_conv(bcx, w, interpret)
    return reference(bcx, w)
