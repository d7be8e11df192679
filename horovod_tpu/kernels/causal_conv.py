"""The depthwise causal convolution of the Mamba-2 and the Gated DeltaNet
mixers, ``y = silu(conv_L(x) + bias)``, forward and backward in one kernel
each.

Both mixers (``models/mamba2.py::Mamba2``, ``models/gated_delta.py::
GatedDeltaNet``) cut a window of channels out of their input projection,
``xBC`` or ``[q ; k ; v]``, and run a filter of ``L`` taps (4) a channel along
the sequence, Mamba-2's with a bias, then a silu:

    c_t = sum_j w[:, j] * x_{t-(L-1)+j} + bias        x = 0 before the sequence
    y_t = c_t * sigmoid(c_t)

There is no product on the MXU in it: 4 B a channel and position forward and
6 B backward, and left to XLA it was five passes over a padded fp32 copy
(``PERF.md`` §6, PR 57).  Here the forward kernel reads ``x`` once and writes
``y`` once; the backward kernel reads ``x`` and ``dy`` once, computes ``c``
again, ``g = dy * silu'(c)``, writes ``dx_t = sum_j w[:, j] * g_{t+(L-1)-j}``
once and adds the taps' and the bias's gradient up in fp32, one partial sum a
sequence.  The residual is ``x`` (where it lies: see ``within``), the taps
and the bias.  Both kernels are bound by the VPU's fp32 lanes and not by
the bytes: at ``[8192, 4352]`` on a v5e 0.43 ms forward and 0.82 backward
where a kernel that only copies through the same blocks takes 0.28, the
exact sigmoid a sixth of the forward and the rows' rotations a twentieth
(my chip runs, PR 57).

**How it goes.**  ``kernels/short_conv.py``'s tiles and halos with a third
grid axis: a grid step takes ``_tile`` positions of one block of
:func:`_columns` channels of one sequence, the ``L - 1`` rows before it from
the 16 rows that end where the tile begins (zero in a sequence's first tile,
so nothing leaks from one sequence of a batch into the next).  The activation
sits between the taps and the output, so the rows behind a tile give ``dx``
their ``g``, not their ``dy``: the backward kernel takes ``x`` and ``dy`` of
the next 16 rows and computes ``c`` there too (zero behind a sequence's last
tile).  The column axis is what lets the kernels read a window of a wider
array in place: ``within=(row, start)`` says that ``x`` is ``row[..., start:
start + c]``, and the blocks are then counted from ``row``'s column block
``start // cols``: no copy of the slice is made for the kernel.

**Precision**: ``x``, ``y`` and ``dx`` in bf16, taps and bias in fp32; sums,
bias, silu and its derivative in fp32, rounded once at the output.
:func:`reference` is the same function in ``jax.numpy`` (each earlier position
a roll of ``x`` under a mask), the path off the TPU, in float32 and for
shapes :func:`takes` refuses.

On the device's op line the calls are :data:`FWD_NAME` and :data:`BWD_NAME`
(``chip_bench/metrics/causal_conv_ms_step.json``).  Pallas is imported where a
kernel is built, not with this module, and each direction is one jitted
function (``kernels/short_conv.py`` says why).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .short_conv import (_HALO, _LANES, _TAP_ROWS, _earlier, _later, _params,
                         _specs, _tap_rows)

# The calls' names on the device's op line, and what matches both.
FWD_NAME = "hvd_causal_conv_fwd"
BWD_NAME = "hvd_causal_conv_bwd"
OP_LINE_NAMES = r"^hvd_causal_conv"

_BIAS_ROW = _TAP_ROWS - 1     # the bias lies behind the taps' rows
_TILE = 512                   # positions a grid step, at most


def takes(seq_len: int, c: int, taps: int, dtype=jnp.bfloat16) -> bool:
    """Whether the kernels take ``x [b, seq_len, c]`` of ``dtype`` and
    ``taps`` taps (with or without a bias); otherwise, and off the TPU,
    :func:`reference`."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and c % _LANES == 0
            and seq_len > 0 and seq_len % _HALO == 0
            and 1 <= taps <= _BIAS_ROW)


def reference(x, w, bias=None):
    """:func:`causal_conv` in ``jax.numpy``: each earlier position a roll of
    ``x`` as it is under a mask of the rows that rolled round, the sums, the
    bias and the silu in fp32, the result in ``x``'s dtype."""
    s, taps = x.shape[1], w.shape[1]
    at = jax.lax.broadcasted_iota(jnp.int32, (1, s, 1), 1)
    w = w.astype(jnp.float32)
    out = w[:, taps - 1] * x.astype(jnp.float32)
    for back in range(1, taps):
        earlier = jnp.where(at >= back, jnp.roll(x, back, axis=1),
                            jnp.zeros_like(x))
        out = out + w[:, taps - 1 - back] * earlier.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return jax.nn.silu(out).astype(x.dtype)


def _columns(c: int, start: int = 0) -> int:
    """Channels a grid step: the widest of 512, 256 and 128 in which both
    the window's width and its first column are whole blocks."""
    return next(cols for cols in (512, 256, _LANES)
                if c % cols == 0 and start % cols == 0)


def _sum(terms):
    """Pairwise, so that the terms are not chained through one sum."""
    while len(terms) > 1:
        terms = [a + b for a, b in zip(terms[::2], terms[1::2])] \
            + terms[len(terms) // 2 * 2:]
    return terms[0]


def _pre(w_ref, x, before, taps: int, biased: bool):
    """(``conv(x) + bias`` down a tile's rows, ``x[t - k]`` a tap): ``x [rows,
    cols]`` in fp32, ``before`` the ``_HALO`` rows that end where it
    begins."""
    shifted = [_earlier(x, before, taps - 1 - j) for j in range(taps)]
    pre = _sum([w_ref[j:j + 1, :] * shifted[j] for j in range(taps)])
    if biased:
        pre = pre + w_ref[_BIAS_ROW:_BIAS_ROW + 1, :]
    return pre, shifted


def _silu_grad(pre):
    """``d silu(pre) / d pre``."""
    sig = jax.nn.sigmoid(pre)
    return sig * (1.0 + pre * (1.0 - sig))


def _fwd_kernel(w_ref, x_ref, before_ref, y_ref, *, taps: int, biased: bool):
    """One tile of one column block of one sequence: taps and bias ``[8,
    cols]``, ``x [tile, cols]``, the ``_HALO`` rows before it, ``y`` out."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    before = jnp.where(pl.program_id(2) == 0, 0.0, before_ref[...].astype(f32))
    pre, _ = _pre(w_ref, x_ref[...].astype(f32), before, taps, biased)
    y_ref[...] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)


def _bwd_kernel(w_ref, x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                dx_ref, dw_ref, *, taps: int, biased: bool):
    """The same tile's cotangents: ``c`` again here and in the ``_HALO``
    rows behind, ``dx [tile, cols]`` written once, the taps' and the bias's
    gradient added to the sequence's ``[8, cols]`` block, which stays where
    it is while the tiles of a sequence go by."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    t = pl.program_id(2)
    first, last = t == 0, t == pl.num_programs(2) - 1

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    x = x_ref[...].astype(f32)
    before = jnp.where(first, 0.0, before_ref[...].astype(f32))
    pre, shifted = _pre(w_ref, x, before, taps, biased)
    g = dy_ref[...].astype(f32) * _silu_grad(pre)
    pre_after, _ = _pre(w_ref, after_ref[...].astype(f32),
                        x[x.shape[0] - _HALO:], taps, biased)
    g_after = jnp.where(
        last, 0.0, dy_after_ref[...].astype(f32) * _silu_grad(pre_after))
    dx_ref[...] = _sum([w_ref[j:j + 1, :] * _later(g, g_after, taps - 1 - j)
                        for j in range(taps)]).astype(dx_ref.dtype)
    for j in range(taps):
        dw_ref[j:j + 1, :] += jnp.sum(g * shifted[j], axis=0, keepdims=True)
    if biased:
        dw_ref[_BIAS_ROW:_BIAS_ROW + 1, :] += jnp.sum(g, axis=0,
                                                      keepdims=True)


def _rows(w, bias):
    """Taps and bias as the kernels' ``[8, c]`` block of fp32."""
    rows = _tap_rows(w)
    return rows if bias is None \
        else rows.at[_BIAS_ROW].set(bias.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("start", "interpret"))
def _forward(row, w, bias, *, start: int, interpret: bool):
    """``row [b, s, width]``, ``w [c, L]``, ``bias [c]`` or None -> ``y [b,
    s, c]`` of ``row``'s columns from ``start`` on.  Jitted: traced once a
    process and lowered once a program, whatever the number of layers."""
    import jax.experimental.pallas as pl

    b, s, _ = row.shape
    c, taps = w.shape
    cols = _columns(c, start)
    first = start // cols
    tile, rows, before, _, tap_rows = _specs(
        s, cols, columns=True, most=_TILE)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, biased=bias is not None),
        grid=(b, c // cols, s // tile),
        in_specs=[tap_rows, rows(cols, first), before(cols, first)],
        out_specs=rows(cols),
        out_shape=jax.ShapeDtypeStruct((b, s, c), row.dtype,
                                       vma=jax.typeof(row).vma),
        compiler_params=_params("parallel", "parallel", "parallel"),
        cost_estimate=pl.CostEstimate(
            flops=2 * (taps + 2) * b * s * c, transcendentals=b * s * c,
            bytes_accessed=4 * b * s * c),
        name=FWD_NAME, interpret=interpret,
    )(_rows(w, bias), row, row)


@functools.partial(jax.jit, static_argnames=("start", "interpret"))
def _backward(row, w, bias, dy, *, start: int, interpret: bool):
    """-> ``(dx [b, s, c], d_w [c, L] in w's dtype, d_bias [c] or None)``."""
    import jax.experimental.pallas as pl

    b, s, _ = row.shape
    c, taps = w.shape
    cols = _columns(c, start)
    first = start // cols
    tile, rows, before, after, tap_rows = _specs(
        s, cols, columns=True, most=_TILE)
    vma = jax.typeof(row).vma
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, biased=bias is not None),
        grid=(b, c // cols, s // tile),
        in_specs=[tap_rows, rows(cols, first), before(cols, first),
                  after(cols, first), rows(cols), after(cols)],
        out_specs=[rows(cols),
                   pl.BlockSpec((None, _TAP_ROWS, cols),
                                lambda i, j, t: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((b, s, c), row.dtype, vma=vma),
                   jax.ShapeDtypeStruct((b, _TAP_ROWS, c), jnp.float32,
                                        vma=vma)],
        # The taps' gradient is added up along a sequence's tiles.
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * (3 * taps + 8) * b * s * c, transcendentals=b * s * c,
            bytes_accessed=6 * b * s * c),
        name=BWD_NAME, interpret=interpret,
    )(_rows(w, bias), row, row, row, dy, dy)
    sums = jnp.sum(sums, axis=0)
    return (dx, sums[:taps].T.astype(w.dtype),
            None if bias is None else sums[_BIAS_ROW].astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _causal_conv(row, w, bias, start, interpret):
    return _forward(row, w, bias, start=start, interpret=interpret)


def _causal_conv_fwd(row, w, bias, start, interpret):
    return (_forward(row, w, bias, start=start, interpret=interpret),
            (row, w, bias))


def _causal_conv_bwd(start, interpret, kept, dy):
    row, w, _ = kept
    dx, d_w, d_bias = _backward(*kept, dy, start=start, interpret=interpret)
    behind = row.shape[-1] - start - w.shape[0]
    # What the slice's own transpose would hand the row's other parts.
    return jnp.pad(dx, ((0, 0), (0, 0), (start, behind))), d_w, d_bias


_causal_conv.defvjp(_causal_conv_fwd, _causal_conv_bwd)


def causal_conv(x, w, bias=None, *, within=None, interpret: bool = False):
    """``silu(conv(x) + bias)`` for ``x [b, s, c]``, the taps ``w [c, L]``
    (tap ``L - 1`` on the position itself) and ``bias [c]`` or None:
    depthwise, causal, zero before each sequence and never across the
    sequences of a batch; ``[b, s, c]`` in ``x``'s dtype.  Differentiable in
    all three.  ``within=(row, start)`` says that ``x`` is ``row[..., start:
    start + c]``: the kernels then read those columns of ``row`` where they
    lie, and nothing reads ``x``.  On a TPU (or with ``interpret``) the
    kernels, for the shapes :func:`takes` takes; else :func:`reference`."""
    _, s, c = x.shape
    if w.shape[0] != c or (bias is not None and bias.shape != (c,)):
        raise ValueError(f"x {x.shape} for taps {w.shape} and bias "
                         f"{None if bias is None else bias.shape}")
    if not ((interpret or jax.default_backend() == "tpu")
            and takes(s, c, w.shape[1], x.dtype)):
        return reference(x, w, bias)
    row, start = (x, 0) if within is None else within
    if row.shape[:2] != x.shape[:2] or start + c > row.shape[2]:
        raise ValueError(f"x {x.shape} within {row.shape} from {start}")
    if start % _LANES:
        row, start = x, 0
    return _causal_conv(row, w, bias, start, interpret)
